"""Layer 2 — compiled-program lint: the paper's mispriced patterns,
checked on the jaxpr + compiled HLO of the programs we actually run.

The paper's central finding is that compiler cost models misprice
exactly the constructs that dominate RVV (and, analogously, lowered-XLA)
performance: predicated/select-heavy code, gather/strided access, and
scan-style ``while`` lowerings that blind the retired-ops counters
(Table 1, reproduced by ``repro.core.counters``).  ``trace_program``
lowers a jitted function once (``repro.core.hlo`` parses the module
text, ``repro.core.compat.cost_dict`` reads the cost channels) and
``lint_trace`` turns the mispriced patterns into the same
:class:`~repro.analysis.findings.Finding` records the source lint emits.

Rules (ids are stable):

``hot-gather`` (warning)
    gather ops in the compiled module — the access pattern the paper's
    Fig-2 shows cost models misprice hardest.  On a decode hot path
    this is usually the paged-KV gather; the finding makes the
    benchmark artifact record that its hot path carries it.  Scatters
    (the per-row KV-cache and output writes every decode step makes)
    are counted apart as ``scatter_ops``: the drift gate pins them, the
    lint does not flag them.

``predication-density`` (warning)
    ``select`` density above threshold — predication-heavy lowering
    (masked/ragged writes, ``jnp.where`` chains) whose per-op cost the
    model treats as free.

``scan-counter-blindness`` (error / info)
    the module lowered to ``while`` bodies: ``cost_analysis()`` counts
    loop bodies ONCE (the paper's broken "vector ins" event), so every
    counter channel read must be gated to ``source="model"`` via
    ``repro.perf.channels`` (``model_flops=``/``model_bytes=``).  Error
    when no analytic model value backs the program, info when one does.

``f32-upcast`` (warning)
    a low-precision (bf16/f16) program whose compiled module is mostly
    f32 instructions — an unintended upcast that doubles bandwidth on
    the memory-bound decode path.

``host-callback`` (error)
    ``pure_callback``/``io_callback``/infeed-style host round-trips
    inside the compiled program — a per-step device sync on the decode
    path.

``missed-donation`` (error)
    ``donate_argnums`` was requested but the compiled module carries no
    input/output aliasing — the donation silently bought nothing and
    the buffer is copied every step.

``analyze_serve_engine`` applies all of this to a
``ContinuousBatchingEngine``'s step functions (the engine's opt-in
``analyze=True`` path) and returns the ``analysis_meta`` block that
serve_bench records in its Report meta.
"""
from __future__ import annotations

import contextlib
import dataclasses
import re
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.findings import Finding
# the rule metadata lives in the stdlib registry so the CLI's --rules
# can list it without importing jax; this module implements them
from repro.analysis.registry import TRACE_RULES  # noqa: F401  (re-export)
from repro.core import hlo as hlo_lib
from repro.core.compat import cost_dict

# `input_output_alias={ {1}: (2, {}, may-alias), ... }` on the module line
_ALIAS_PAIR_RE = re.compile(r"\(\d+,\s*\{[^{}]*\},\s*(?:may|must)-alias\)")
_LOW_PRECISION = ("bfloat16", "float16")


@dataclasses.dataclass
class TraceReport:
    """Everything ``lint_trace`` needs about one compiled program."""

    label: str
    op_histogram: Dict[str, int]
    instruction_classes: Dict[str, int]
    while_bodies: int
    primitives: Tuple[str, ...]          # jaxpr primitive names (recursive)
    input_dtypes: Tuple[str, ...]
    f32_instrs: int                      # instructions with an f32 result
    typed_instrs: int
    alias_pairs: int                     # input/output aliasing entries
    donated: bool                        # donation was requested
    cost: Dict[str, Any]                 # raw cost_dict channels

    @property
    def total_ops(self) -> int:
        return sum(self.op_histogram.values())

    @property
    def select_frac(self) -> float:
        return self.op_histogram.get("select", 0) / max(1, self.total_ops)

    @property
    def gather_ops(self) -> int:
        return sum(n for op, n in self.op_histogram.items()
                   if op.startswith("gather"))

    @property
    def scatter_ops(self) -> int:
        return sum(n for op, n in self.op_histogram.items()
                   if op.startswith("scatter"))

    def summary(self) -> Dict[str, Any]:
        return {"label": self.label, "total_ops": self.total_ops,
                "while_bodies": self.while_bodies,
                "gather_ops": self.gather_ops,
                "scatter_ops": self.scatter_ops,
                "select_frac": round(self.select_frac, 4),
                "f32_instr_frac": round(
                    self.f32_instrs / max(1, self.typed_instrs), 4),
                "alias_pairs": self.alias_pairs, "donated": self.donated,
                "instruction_classes": dict(self.instruction_classes)}


def _jaxpr_primitives(closed) -> Tuple[str, ...]:
    """All primitive names in a (closed) jaxpr, recursing into sub-jaxprs
    (scan/while/cond bodies, pjit calls)."""
    from jax.extend import core

    seen: set = set()

    def walk(jxp) -> None:
        jxp = getattr(jxp, "jaxpr", jxp)
        for eqn in jxp.eqns:
            seen.add(eqn.primitive.name)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    if isinstance(sub, (core.Jaxpr, core.ClosedJaxpr)):
                        walk(sub)
    walk(closed)
    return tuple(sorted(seen))


def _f32_instr_counts(text: str) -> Tuple[int, int]:
    n_f32 = n_typed = 0
    for line in text.splitlines():
        m = hlo_lib._INSTR_RE.match(line)
        if not m:
            continue
        type_str, opcode = m.group(2), m.group(3)
        if opcode in ("parameter", "constant", "get-tuple-element", "tuple"):
            continue
        n_typed += 1
        if "f32[" in type_str:
            n_f32 += 1
    return n_f32, n_typed


def trace_program(fn, *args, donate_argnums: Sequence[int] = (),
                  static_argnums: Sequence[int] = (),
                  label: str = "fn", compiled=None) -> TraceReport:
    """Lower + compile ``fn(*args)`` (args may be ShapeDtypeStructs) and
    extract the pattern channels the trace rules consume.

    ``compiled`` short-circuits compilation when the caller already holds
    the executable; ``donate_argnums`` must still be passed so the
    missed-donation rule knows donation was *requested*.
    """
    donate = tuple(donate_argnums)
    static = tuple(static_argnums)
    with warnings.catch_warnings():
        # unusable-donation warnings are our finding, not console noise
        warnings.simplefilter("ignore")
        closed = jax.make_jaxpr(fn, static_argnums=static)(*args)
        comp = compiled if compiled is not None else jax.jit(
            fn, donate_argnums=donate, static_argnums=static
        ).lower(*args).compile()
    text = comp.as_text()
    rep = hlo_lib.analyze_hlo(text)
    f32_instrs, typed_instrs = _f32_instr_counts(text)
    dtypes = tuple(sorted({str(leaf.dtype)
                           for leaf in jax.tree_util.tree_leaves(args)
                           if hasattr(leaf, "dtype")}))
    return TraceReport(
        label=label, op_histogram=rep.op_histogram,
        instruction_classes=hlo_lib.instruction_classes(rep.op_histogram),
        while_bodies=rep.while_bodies,
        primitives=_jaxpr_primitives(closed), input_dtypes=dtypes,
        f32_instrs=f32_instrs, typed_instrs=typed_instrs,
        alias_pairs=len(_ALIAS_PAIR_RE.findall(text)), donated=bool(donate),
        cost=cost_dict(comp))


def lint_trace(report: TraceReport, *,
               model_values_supplied: bool = False,
               verdicts: Optional[Dict[str, bool]] = None,
               gather_threshold: int = 1,
               select_frac_threshold: float = 0.15,
               f32_frac_threshold: float = 0.25) -> List[Finding]:
    """Apply every trace rule to one :class:`TraceReport`."""
    path = f"<trace:{report.label}>"
    findings: List[Finding] = []

    n_gather = report.gather_ops
    if n_gather >= gather_threshold:
        findings.append(Finding(
            "hot-gather", "warning", path, 0,
            f"{n_gather} gather op(s) in the compiled module — "
            "the strided/gather access pattern compiler cost models "
            "misprice hardest (paper Fig-2); expected for paged-KV "
            "decode, but the artifact should say so",
            context={"gather_ops": n_gather,
                     "total_ops": report.total_ops}))

    frac = report.select_frac
    if frac >= select_frac_threshold:
        findings.append(Finding(
            "predication-density", "warning", path, 0,
            f"select density {frac:.2f} >= {select_frac_threshold:.2f} — "
            "predication-heavy lowering (masked/ragged writes); the cost "
            "model prices selects as free ALU while they serialize "
            "vector lanes",
            context={"select_ops": report.op_histogram.get("select", 0),
                     "total_ops": report.total_ops}))

    if report.while_bodies > 0:
        verdict = (verdicts or {}).get("flops_scan")
        sev = "info" if model_values_supplied else "error"
        backing = ("analytic model values supplied — channel reads gate "
                   "to source=\"model\"" if model_values_supplied else
                   "NO analytic model value backs this program — counter "
                   "reads are silently wrong; pass model_flops=/"
                   "model_bytes= through repro.perf.channels")
        findings.append(Finding(
            "scan-counter-blindness", sev, path, 0,
            f"{report.while_bodies} while body(ies): cost_analysis() "
            "counts loop bodies once (Table-1 flops_scan verdict: "
            f"{verdict}); {backing}",
            context={"while_bodies": report.while_bodies,
                     "flops_scan_verdict": verdict}))

    low = [d for d in report.input_dtypes if d in _LOW_PRECISION]
    f32_frac = report.f32_instrs / max(1, report.typed_instrs)
    if low and f32_frac >= f32_frac_threshold:
        findings.append(Finding(
            "f32-upcast", "warning", path, 0,
            f"inputs are {low} but {f32_frac:.0%} of compiled "
            "instructions produce f32 — an unintended upcast doubles "
            "HBM traffic on the memory-bound path",
            context={"input_dtypes": list(report.input_dtypes),
                     "f32_instr_frac": round(f32_frac, 4)}))

    cb_prims = [p for p in report.primitives if "callback" in p]
    cb_ops = [op for op in ("infeed", "outfeed", "send", "recv")
              if op in report.op_histogram]
    if cb_prims or cb_ops:
        findings.append(Finding(
            "host-callback", "error", path, 0,
            f"host round-trip inside the compiled program "
            f"(primitives={cb_prims or cb_ops}) — a per-step device sync "
            "on the decode hot path",
            context={"primitives": cb_prims, "ops": cb_ops}))

    if report.donated and report.alias_pairs == 0:
        findings.append(Finding(
            "missed-donation", "error", path, 0,
            "donate_argnums was requested but the compiled module "
            "carries no input/output aliasing — the donated operand is "
            "absent from output aliasing and gets copied every call",
            context={"alias_pairs": 0}))

    return findings


# ---------------------------------------------------------------------------
# serve-engine integration (ContinuousBatchingEngine(analyze=True))
# ---------------------------------------------------------------------------
def serve_step_args(engine) -> Dict[str, Any]:
    """ShapeDtypeStruct argument tuples for ``engine``'s step programs —
    the exact shapes the scheduler emits, with no device work.

    Returns ``{"decode": args, "prefill": args, "paged": bool,
    "ctx": context-factory}`` where ``ctx()`` is the sharding context the
    programs must trace under (a nullcontext off-mesh).  Shared between
    ``analyze_serve_engine`` and ``repro.analysis.fingerprint`` so the
    fingerprinted programs are exactly the analyzed ones.
    """
    model = engine.model
    n, L = engine.n_slots, engine.max_len
    chunk = engine.sched.prefill_chunk
    sds = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    params_s = jax.tree_util.tree_map(
        lambda x: sds(jnp.shape(x), x.dtype), engine.params)
    cache_s = jax.eval_shape(lambda: model.init_cache(n, L))
    out_s = sds((engine._n_out_rows, L), i32)
    prev_s = sds((n,), i32)
    # decode: (params, cache, out_buf, prev_sampled, tokens, token_src,
    #          positions, n_valid, temperatures, out_rows, out_idx,
    #          step_idx, any_temp[static][, page_idx])
    # speculative engines feed 1 + spec_k token/position columns per
    # decode row (the verify step); plain engines keep width 1
    w = 1 + (engine.spec_k if getattr(engine, "spec_decode", False) else 0)
    decode_args = (params_s, cache_s, out_s, prev_s, sds((n, w), i32),
                   sds((n,), jnp.bool_), sds((n, w), i32), sds((n,), i32),
                   sds((n,), f32), sds((n,), i32), sds((n,), i32),
                   sds((), i32), False)
    paged = bool(getattr(engine, "paged_kernel", False))
    if paged:
        # the paged engine's decode step takes the page-index device
        # array as a trailing argument (any_temp stays static at 12)
        decode_args = decode_args + (
            sds(tuple(engine._page_idx.shape), i32),)
    # prefill row: (params, cache, out_buf, prev_sampled, slot, tokens,
    #               positions, n_valid, temperature, out_row, out_idx,
    #               step_idx, any_temp[static])
    prefill_args = (params_s, cache_s, out_s, prev_s, sds((), i32),
                    sds((1, chunk), i32), sds((1, chunk), i32),
                    sds((1,), i32), sds((), f32), sds((), i32),
                    sds((), i32), sds((), i32), False)
    if engine.mesh is not None:
        from repro.parallel import axes as paxes
        ctx = lambda: paxes.sharding_ctx(engine.mesh, engine.rules)  # noqa: E731
    else:
        ctx = contextlib.nullcontext
    return {"decode": decode_args, "prefill": prefill_args,
            "paged": paged, "ctx": ctx}


def analyze_serve_engine(engine, *, calibration=None) -> Dict[str, Any]:
    """Trace-lint a ``ContinuousBatchingEngine``'s step programs.

    Lowers the engine's decode step and prefill row against the exact
    shapes the scheduler emits (ShapeDtypeStructs — no device work
    beyond compilation), runs every trace rule, and returns the
    ``analysis_meta`` block: per-program findings + pattern summary plus
    the Table-1 verdicts the rules were judged under.  The engine's
    analytic StepCostModel backs its stats, so scan-lowered families
    report ``scan-counter-blindness`` at info severity (the counters are
    already forced to ``source="model"``).
    """
    from repro.analysis.fingerprint import fingerprint_report
    from repro.perf import channels as perf_channels

    cal = (calibration if calibration is not None
           else perf_channels.default_calibration())
    sa = serve_step_args(engine)
    ctx, paged = sa["ctx"], sa["paged"]

    programs: Dict[str, Any] = {}
    n_findings = 0
    worst = None
    rank = {"info": 0, "warning": 1, "error": 2}
    decode_fn = (engine._make_spec_decode_fn()
                 if getattr(engine, "spec_decode", False)
                 else engine._make_decode_fn())
    for label, fn, args in (
            ("decode_step", decode_fn, sa["decode"]),
            ("prefill_row", engine._make_prefill_fn(), sa["prefill"])):
        with ctx():
            rep = trace_program(fn, *args, donate_argnums=(1, 2, 3),
                                static_argnums=(12,), label=label)
        fs = lint_trace(rep, model_values_supplied=True,
                        verdicts=cal.verdicts)
        n_findings += len(fs)
        for f in fs:
            if worst is None or rank[f.severity] > rank[worst]:
                worst = f.severity
        programs[label] = {"findings": [f.row() for f in fs],
                           "fingerprint": fingerprint_report(
                               rep, verdicts=cal.verdicts, findings=fs),
                           **rep.summary()}
    return {"rules": sorted(TRACE_RULES),
            "verdicts": dict(cal.verdicts),
            "programs": programs,
            "n_findings": n_findings,
            "worst_severity": worst,
            "paged_kernel": paged,
            "paged": getattr(engine, "paged_meta", None)}
