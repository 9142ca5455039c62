"""Canonical program fingerprints — the compile-drift contract.

The paper's method is to pin *compiled-program shape* against calibrated
counters so a compiler (or a refactor) silently regressing into a
mispriced pattern — a gather on the decode hot path, a dropped donation
alias, an unexpected while-lowering — is caught as drift, not discovered
in a benchmark three releases later.  :func:`fingerprint_report` reduces
a :class:`~repro.analysis.trace.TraceReport` to a canonical, JSON-stable
dict; :func:`collect_fingerprints` builds the live fingerprints of every
**pinned program** — the serve hot paths (paged decode step, its XLA
identity-layout twin, the prefill row, the frontend-driven step) and the
kernel-family ops at fixed tiny shapes — which ``repro.analysis.diff``
compares against the checked-in baselines under
``src/repro/analysis/baselines/*.json``.

The fingerprint deliberately records *shape*, not *wall*: op histogram
and gather/select densities (the Fig-2 mispriced patterns),
counter-verdict-tagged flops/bytes from ``compat.cost_dict`` (tagged
``model-required`` when while-bodies blind the counters, per the Table-1
``flops_scan`` verdict), input/output donation aliasing, input dtypes,
and which trace-lint rules fire.  Everything here is deterministic under
a fixed jax version; walls never enter, so the gate is immune to CPU
noise.

Update procedure: ``python -m repro.analysis --update-baselines`` after
an *intentional* program change, commit the rewritten JSON with the PR
that changed the program.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Optional, Sequence

from repro.analysis.trace import (TraceReport, lint_trace, serve_step_args,
                                  trace_program)

FINGERPRINT_VERSION = 1

#: every pinned program, in baseline-file order.  serve.* come from tiny
#: reduced-config engines (the same build as tests/test_analysis.py's
#: analyze-meta test); kernels.* are the kernel-family ops at fixed tiny
#: shapes.  ``frontend_step`` is the decode program of a
#: stall-free-chunk-policy engine — the configuration the open-loop
#: frontend (serve/frontend.py) drives.
TARGETS = (
    "serve.decode_step.paged",
    "serve.decode_step.xla",
    "serve.decode_step.spec",
    "serve.prefill_row",
    "serve.frontend_step",
    "kernels.gemm",
    "kernels.flash_attention",
    "kernels.paged_attention.xla",
)


def fingerprint_report(rep: TraceReport, *,
                       verdicts: Optional[Dict[str, bool]] = None,
                       findings: Iterable[Any] = (),
                       sharding: Optional[Dict[str, Any]] = None
                       ) -> Dict[str, Any]:
    """Reduce one traced program to its canonical fingerprint dict.

    JSON-stable: every container is sorted, every float rounded, so
    ``json.dumps(..., sort_keys=True)`` of the same program is
    byte-identical run to run.
    """
    cost = rep.cost or {}
    flops = float(cost.get("flops", 0.0))
    bytes_ = float(cost.get("bytes accessed", 0.0))
    return {
        "version": FINGERPRINT_VERSION,
        "label": rep.label,
        "op_histogram": {k: int(v) for k, v in
                         sorted(rep.op_histogram.items())},
        "instruction_classes": {k: int(v) for k, v in
                                sorted(rep.instruction_classes.items())},
        "total_ops": int(rep.total_ops),
        "gather_ops": int(rep.gather_ops),
        "scatter_ops": int(rep.scatter_ops),
        "select_frac": round(rep.select_frac, 4),
        "while_bodies": int(rep.while_bodies),
        "f32_instr_frac": round(
            rep.f32_instrs / max(1, rep.typed_instrs), 4),
        "input_dtypes": sorted(rep.input_dtypes),
        "donated": bool(rep.donated),
        "alias_pairs": int(rep.alias_pairs),
        "counters": {
            "flops": flops,
            "bytes": bytes_,
            # while-lowered programs blind the retired-ops counters
            # (Table-1 flops_scan): their counter numbers are only valid
            # backed by analytic model values
            "verdict": ("model-required" if rep.while_bodies
                        else "counter"),
            "flops_scan_verdict": (verdicts or {}).get("flops_scan"),
        },
        "finding_rules": sorted({f.rule for f in findings}),
        "sharding": sharding,
    }


# ---------------------------------------------------------------------------
# live collection of the pinned programs
# ---------------------------------------------------------------------------
def _serve_engines(names) -> Dict[str, Any]:
    """Build the tiny reduced-config engines backing the serve.* targets
    (shared model/params; one engine per traced configuration)."""
    import jax

    from repro.configs import reduced_config
    from repro.models import build_model
    from repro.serve.engine import ContinuousBatchingEngine

    cfg = reduced_config("granite-3-2b")
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    kw = dict(n_slots=2, max_len=32, prefill_chunk=8)
    engines: Dict[str, Any] = {}
    if names & {"serve.decode_step.paged", "serve.prefill_row"}:
        engines["paged"] = ContinuousBatchingEngine(model, params, **kw)
    if "serve.decode_step.xla" in names:
        engines["xla"] = ContinuousBatchingEngine(
            model, params, paged_kernel=False, **kw)
    if "serve.decode_step.spec" in names:
        # the speculative verify step (serve/draft.py draft-verify):
        # 1 + spec_k query columns per decode row, gather-free
        # acceptance + ragged commit — pinned so the accept/commit
        # lowering cannot silently regress into a gather
        engines["spec"] = ContinuousBatchingEngine(
            model, params, spec_decode=True, spec_k=4, **kw)
    if "serve.frontend_step" in names:
        engines["frontend"] = ContinuousBatchingEngine(
            model, params, chunk_policy="stall_free", tbt_target_s=0.05,
            **kw)
    return engines


def _trace_engine_program(engine, which: str, label: str, verdicts
                          ) -> Dict[str, Any]:
    sa = serve_step_args(engine)
    fn = (engine._make_prefill_fn() if which == "prefill"
          else engine._make_spec_decode_fn()
          if getattr(engine, "spec_decode", False)
          else engine._make_decode_fn())
    with sa["ctx"]():
        rep = trace_program(fn, *sa[which], donate_argnums=(1, 2, 3),
                            static_argnums=(12,), label=label)
    fs = lint_trace(rep, model_values_supplied=True, verdicts=verdicts)
    return fingerprint_report(rep, verdicts=verdicts, findings=fs)


def _kernel_fingerprint(name: str, verdicts) -> Dict[str, Any]:
    """One kernel-family op at a fixed tiny shape (f32 inputs so the
    fingerprint isolates op structure from precision findings)."""
    import jax
    import jax.numpy as jnp

    sds = jax.ShapeDtypeStruct
    f32, i32 = jnp.float32, jnp.int32
    if name == "kernels.gemm":
        from repro.kernels.gemm import ops

        def fn(a, b):
            return ops.gemm(a, b, bk=16)

        args = (sds((16, 32), f32), sds((32, 16), f32))
    elif name == "kernels.flash_attention":
        from repro.kernels.flash_attention import ops

        def fn(q, k, v):
            return ops.flash_attention(q, k, v, block_q=8, block_kv=8)

        args = (sds((1, 8, 4, 8), f32), sds((1, 8, 2, 8), f32),
                sds((1, 8, 2, 8), f32))
    elif name == "kernels.paged_attention.xla":
        from repro.kernels.paged_attention import ops

        def fn(q, kp, vp, page_idx, positions, kv_valid):
            # the engine's identity-layout specialization: pool pages
            # B * pages_per_seq, row-major — the impl the hot path runs
            # on host/CPU backends
            return ops.paged_attention(q, kp, vp, page_idx, positions,
                                       kv_valid, page_size=16, impl="xla")

        args = (sds((2, 1, 4, 8), f32), sds((4, 16, 2, 8), f32),
                sds((4, 16, 2, 8), f32), sds((2, 2), i32),
                sds((2, 1), i32), sds((2,), i32))
    else:
        raise KeyError(f"unknown kernel fingerprint target {name!r}")
    rep = trace_program(fn, *args, label=name)
    fs = lint_trace(rep, model_values_supplied=True, verdicts=verdicts)
    return fingerprint_report(rep, verdicts=verdicts, findings=fs)


def collect_fingerprints(targets: Optional[Sequence[str]] = None, *,
                         calibration=None) -> Dict[str, Dict[str, Any]]:
    """Live fingerprints of the pinned programs ({name: fingerprint}).

    ``targets`` restricts collection (default: all of :data:`TARGETS`);
    unknown names raise.  Compilation only — no device execution beyond
    the paged-kernel autotune (which is disk-cached).
    """
    from repro.perf import channels as perf_channels

    names = list(targets) if targets is not None else list(TARGETS)
    unknown = sorted(set(names) - set(TARGETS))
    if unknown:
        raise KeyError(f"unknown fingerprint target(s) {unknown}; "
                       f"pinned programs are {list(TARGETS)}")
    cal = (calibration if calibration is not None
           else perf_channels.default_calibration())
    verdicts = cal.verdicts
    out: Dict[str, Dict[str, Any]] = {}
    wanted = set(names)
    engines = _serve_engines(wanted)
    if "serve.decode_step.paged" in wanted:
        out["serve.decode_step.paged"] = _trace_engine_program(
            engines["paged"], "decode", "serve.decode_step.paged", verdicts)
    if "serve.decode_step.xla" in wanted:
        out["serve.decode_step.xla"] = _trace_engine_program(
            engines["xla"], "decode", "serve.decode_step.xla", verdicts)
    if "serve.decode_step.spec" in wanted:
        out["serve.decode_step.spec"] = _trace_engine_program(
            engines["spec"], "decode", "serve.decode_step.spec", verdicts)
    if "serve.prefill_row" in wanted:
        out["serve.prefill_row"] = _trace_engine_program(
            engines["paged"], "prefill", "serve.prefill_row", verdicts)
    if "serve.frontend_step" in wanted:
        out["serve.frontend_step"] = _trace_engine_program(
            engines["frontend"], "decode", "serve.frontend_step", verdicts)
    for name in names:
        if name.startswith("kernels."):
            out[name] = _kernel_fingerprint(name, verdicts)
    return {k: out[k] for k in names}
