"""Serving throughput: continuous batching (paged decode state, chunked
prefill) vs the fixed-batch run-to-completion baseline — per family.

For each workload mix (slots x prompt-length band x generation-length
band) the same request set runs through both engines:

  * static  — requests grouped into fixed batches of ``slots``; prompts
    right-padded to the batch max; every wave decodes to the *longest*
    generation in the wave (the pre-continuous-batching deployment).
  * continuous — all requests queued up front; slots recycle the moment a
    request finishes, prefills ride along in bounded chunks.

``--families all`` (or a comma list: ``--families lm,ssm,vlm``) runs the
high-variance ``mixed_gens`` mix through every family's smallest config
via the DecodeState protocol; without the flag the three classic mixes
run on the lm config.  CPU wall timings on this class of box swing ±50%
between processes, so both engines run REPEATS *interleaved* passes
through ``repro.perf.measure`` (the continuous engine's reset/submit
happen as untimed per-repeat setup — only the drain is timed) and the
artifact reports the **median** wall/tok-per-s (plus every raw wall) —
trust orderings and medians, never a single number.

Each engine row also carries the analytic work executed (engine-stats
``model_flops``/``model_bytes`` from core/costmodel via the engines'
StepCostModel) and the derived ``roofline_utilization`` — the modeled
bound time divided by the measured wall (``repro.perf.report.
roofline_fraction``) — so per-family speedups are roofline-attributable,
not just tokens/s.  Rows land in benchmarks/results/serve_bench.json in
the canonical Report schema.

The **shared-prefix scenario** (always appended on the lm run; run at
tiny shapes under ``REPRO_BENCH_SMOKE=1``) serves a workload whose
requests share a long common prompt prefix through two continuous
engines — prefix cache on vs off — interleaved through
``perf.measure``; rows report ``prefix_hit_tokens`` / ``prefix_hit_rate``
and ``speedup_vs_nocache``.  The paper's premise makes this the
highest-leverage serve optimization: prefill-style compute is exactly
where RVV autovectorization is weakest, so the best prefill is the one
the page table lets you skip.

The **paged-kernel scenario** (appended on the lm run and on the CI
smoke) races the fused paged flash-decode attention kernel (engine
default) against the dense XLA gather-then-attend decode
(``paged_kernel=False``) on the high-variance mix, both with
``analyze=True``: rows carry ``speedup_vs_xla`` and
``roofline_utilization``; the Report meta's ``paged`` block carries
each contender's compiled-program trace-lint verdict, the
expected-findings contract (baseline decode must show ``hot-gather``,
paged decode must not), and the autotuned ``block_pages`` pick from
``benchmarks/results/autotune_cache.json`` (``--retune`` re-measures).

The **sharded scenario** (``--sharded``; its own
``serve_bench_sharded.json`` artifact) runs the same workload through
mesh-sharded continuous engines at 1 / 2 / 4 slot shards as equal
interleaved contenders — tok/s and roofline_utilization per shard
count, ``speedup_vs_1shard``, and each engine's resolved layout
(rules + forced-replication decisions from ``parallel.sharding``) in
the Report meta.  Shard counts needing more devices than the host
exposes are skipped with a note (fake devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``); ``--sp-kv``
uses (data x model) meshes and shards the KV sequence axis too.

The **open-loop scenario** (``--open-loop``; its own
``serve_bench_open_loop.json`` artifact) measures the *latency* side:
the workload arrives as a Poisson process at three rates bracketing the
calibrated closed-loop capacity (plus a fixed-trace replay contender),
driven through ``repro.serve.OpenLoopFrontend``'s virtual clock.  Rows
carry the schema-validated ``latency`` block — TTFT/TBT/E2E
p50/p90/p99, queue depth over time, and goodput under a derived
TTFT+TBT SLO — next to the usual throughput and roofline columns.

The shared-prefix baseline engine builds with ``analyze=True``, so the
Report meta's ``analysis`` block records the ``repro.analysis.trace``
cost-model lint (hot gathers, counter-blind scans, donation, ...) for
the very compiled decode/prefill programs the rows time — the artifact
says both how fast the step ran and what the compiler did to it.
"""
from __future__ import annotations

import argparse
import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.configs import reduced_config
from repro.core.compile_cache import use_compile_cache
from repro.launch.mesh import AxisType, make_mesh
from repro.models import build_model
from repro.models.decode_state import stub_context
from repro.perf.measure import measure as perf_measure
from repro.perf.measure import measure_group
from repro.perf.report import roofline_fraction
from repro.serve import (SLO, ContinuousBatchingEngine, OpenLoopFrontend,
                         StaticBatchEngine)
from repro.serve.arrivals import (poisson_arrivals, synthetic_requests,
                                  trace_arrivals, trace_payload)

ARCH = "granite-3-2b"

# smallest config per family (the per-family parity smoke set)
FAMILY_ARCHS = {
    "lm": "granite-3-2b",
    "ssm": "mamba2-780m",
    "hybrid": "jamba-v0.1-52b",
    "vlm": "llama-3.2-vision-90b",
    "audio": "whisper-base",
}

#          name        slots prompt-band  gen-band   requests
MIXES = [("uniform",       4, (24, 25),   (16, 17),   8),
         ("mixed_prompts", 4, (8, 33),    (16, 17),   8),
         ("mixed_gens",    4, (8, 33),    (2, 97),   24)]
HIGH_VARIANCE_MIX = MIXES[2]

REPEATS = 3          # interleaved passes; medians reported

# shared-prefix workload: slots, shared prompt-prefix len, tail band,
# gen band, requests.  The smoke variant keeps --bench-smoke under the
# CI budget while still producing hits (prefix spans 2 pages).
PREFIX_SCENARIO = dict(slots=4, shared_len=40, tail_band=(4, 13),
                       gen_band=(8, 17), n_req=12)
PREFIX_SCENARIO_SMOKE = dict(slots=2, shared_len=16, tail_band=(2, 6),
                             gen_band=(3, 6), n_req=6)

# sharded scenario: slot-shard counts raced as interleaved contenders
# (slots must divide by every count that runs; counts needing more
# devices than the host exposes are skipped with a note)
SHARD_COUNTS = (1, 2, 4)
SHARDED_SCENARIO = dict(slots=4, prompt_band=(8, 29), gen_band=(8, 25),
                        n_req=12)
SHARDED_SCENARIO_SMOKE = dict(slots=2, prompt_band=(4, 9), gen_band=(3, 6),
                              n_req=4)

# paged-kernel scenario: the same workload through two continuous
# engines — paged flash-decode kernel vs the XLA gather-then-attend
# baseline (paged_kernel=False) — as equal interleaved contenders.
# Full shapes reuse the high-variance mixed_gens bands; both engines
# build with analyze=True so the Report meta carries the trace-lint
# split (hot-gather present on the baseline decode, absent on paged).
PAGED_SCENARIO = dict(slots=4, prompt_band=(8, 33), gen_band=(2, 97),
                      n_req=24)
PAGED_SCENARIO_SMOKE = dict(slots=2, prompt_band=(4, 9), gen_band=(3, 6),
                            n_req=6)

# open-loop scenario (--open-loop; its own serve_bench_open_loop.json
# artifact): the same workload arrives as a Poisson process at three
# rates bracketing the closed-loop throughput knee (the drain capacity
# in requests/s, calibrated first on the same engine), plus one
# fixed-trace contender that replays the mid-rate arrivals through the
# repro.serve.trace schema round trip.  All contenders run interleaved
# through measure_group; each row carries the full ``latency`` block
# (TTFT/TBT/E2E percentiles, queue depth, goodput under a derived SLO).
OPEN_LOOP_SCENARIO = dict(slots=4, prompt_band=(8, 25), gen_band=(8, 25),
                          n_req=16, rate_factors=(0.5, 1.0, 2.0))
OPEN_LOOP_SCENARIO_SMOKE = dict(slots=2, prompt_band=(4, 9),
                                gen_band=(3, 6), n_req=5,
                                rate_factors=(0.5, 1.0, 2.0))

# speculative scenario (--speculative; its own serve_bench_speculative
# artifact): the same workload through two continuous engines — n-gram
# draft-verify speculation on vs off — as equal interleaved contenders,
# on two prompt mixes: ``repetitive`` (every prompt tiles a short token
# motif — the prompt-lookup drafter's best case, proposals fire from the
# first decode step) and ``random`` (i.i.d. prompts — the drafter can
# only lock onto the model's own greedy cycles mid-generation).  Rows
# carry the per-family accept_rate next to tok/s and
# ``speedup_vs_nonspec``; generation is temperature 0 because the
# scheduler only drafts for greedy rows (speculation preserves exact
# token parity, so spec and baseline emit identical tokens).
SPEC_SCENARIO = dict(slots=4, prompt_band=(8, 17), gen_band=(96, 97),
                     motif_len=2, n_req=8, spec_k=6)
SPEC_SCENARIO_SMOKE = dict(slots=2, prompt_band=(6, 9), gen_band=(48, 49),
                           motif_len=2, n_req=4, spec_k=6)


def _workload(rng, n, p_band, g_band, vocab):
    reqs = []
    for _ in range(n):
        plen = int(rng.integers(*p_band))
        glen = int(rng.integers(*g_band))
        reqs.append((rng.integers(1, vocab, size=plen), glen))
    return reqs


def _static_pass(engine, reqs, slots, pad_to, extra=None):
    """One full static pass; returns (generated, model_flops, model_bytes).
    Wall timing happens in the caller via repro.perf.measure."""
    f0, b0 = engine.stats.model_flops, engine.stats.model_bytes
    generated = 0
    for w0 in range(0, len(reqs), slots):
        wave = reqs[w0:w0 + slots]
        while len(wave) < slots:                 # ragged tail wave: pad rows
            wave = wave + [wave[-1]]
        batch = np.zeros((slots, pad_to), np.int32)
        for i, (p, _) in enumerate(wave):
            batch[i, :len(p)] = p                # right-pad to fixed width
        n_steps = max(g for _, g in wave)        # wave runs to the longest
        out = engine.generate(jnp.asarray(batch), n_steps=n_steps,
                              extra=extra)
        jax.block_until_ready(out)
        generated += sum(g for _, g in reqs[w0:w0 + slots])
    return generated, engine.stats.model_flops - f0, \
        engine.stats.model_bytes - b0


def _run_pair(model, params, reqs, slots, max_len, *,
              page_size=8, prefill_chunk=32):
    """Time both engines on the same workload through repro.perf.measure:
    the passes run as interleaved contenders (static, continuous, static,
    ...) so CPU noise hits both alike; the REPEATS walls are medianed per
    engine.  The continuous engine's reset + submit runs as the
    contender's untimed per-repeat ``setup`` — only ``run()`` (the drain)
    is inside the timed region, matching the static engine whose timed
    region is likewise pure serving work."""
    rng = np.random.default_rng(11)
    cfg = model.cfg
    extra_b = stub_context(cfg, rng, batch=slots)
    extra_1 = (None if extra_b is None
               else {k: v[0] for k, v in extra_b.items()})
    if extra_b is not None:
        extra_b = {k: jnp.asarray(v) for k, v in extra_b.items()}

    static = StaticBatchEngine(model, params, max_len=max_len, batch=slots)
    pad_to = max(len(p) for p, _ in reqs)
    jax.block_until_ready(                       # warm both jitted shapes
        static.generate(jnp.ones((slots, pad_to), jnp.int32), n_steps=2,
                        extra=extra_b))
    cont = ContinuousBatchingEngine(
        model, params, n_slots=slots, max_len=max_len,
        page_size=page_size, prefill_chunk=prefill_chunk)
    cont.submit(np.ones(prefill_chunk + 2, np.int32), 3, extra=extra_1)
    cont.run()                                   # warm both step widths

    def _cont_setup():
        cont.reset()
        for prompt, glen in reqs:
            cont.submit(prompt, glen, extra=extra_1)

    m = perf_measure(
        lambda: _static_pass(static, reqs, slots, pad_to, extra=extra_b),
        reps=REPEATS, warmup=0, jit=False,
        interleave_with={"continuous": (cont.run, (), _cont_setup)})
    mc = m.interleaved["continuous"]

    generated, st_flops, st_bytes = m.result     # per-pass deltas
    ct_summary = cont.stats.summary()            # last pass (reset per rep)
    st = {"tok_per_s": generated / m.median_s,
          "wall_s_median": m.median_s,
          "wall_s_all": [round(w, 4) for w in m.all_s],
          "generated_tokens": generated,
          "model_flops": st_flops, "model_bytes": st_bytes,
          "roofline_utilization": roofline_fraction(
              st_flops, st_bytes, m.median_s)}
    ct = {"tok_per_s": ct_summary["generated_tokens"] / mc.median_s,
          "wall_s_median": mc.median_s,
          "wall_s_all": [round(w, 4) for w in mc.all_s],
          "generated_tokens": ct_summary["generated_tokens"],
          "step_ms_p50": ct_summary["step_ms_p50"],
          "step_ms_p95": ct_summary["step_ms_p95"],
          "mean_occupancy": ct_summary["mean_occupancy"],
          "model_flops": ct_summary["model_flops"],
          "model_bytes": ct_summary["model_bytes"],
          "roofline_utilization": roofline_fraction(
              ct_summary["model_flops"], ct_summary["model_bytes"],
              mc.median_s)}
    return st, ct


def _prefix_rows(cfg, model, params, sc: Dict, family: str = "lm"
                 ) -> Tuple[List[Dict], Dict]:
    """Shared-prefix workload through two continuous engines — prefix
    cache on vs off — as equal interleaved contenders (measure_group):
    reset + re-submit runs as each contender's untimed per-repeat setup,
    only the drain is timed.

    The baseline (no-cache) engine is built with ``analyze=True``, so
    the returned ``(rows, analysis)`` pair carries the trace-lint
    verdict on the exact compiled decode/prefill programs being timed;
    ``run`` records it in the Report meta."""
    page = 8
    rng = np.random.default_rng(13)
    shared = rng.integers(1, cfg.vocab_size, size=sc["shared_len"])
    reqs = []
    for _ in range(sc["n_req"]):
        tail = rng.integers(1, cfg.vocab_size,
                            size=int(rng.integers(*sc["tail_band"])))
        reqs.append((np.concatenate([shared, tail]),
                     int(rng.integers(*sc["gen_band"]))))
    longest = max(len(p) + g for p, g in reqs)
    max_len = -(-longest // page) * page

    engines = {
        "prefix_cache": ContinuousBatchingEngine(
            model, params, n_slots=sc["slots"], max_len=max_len,
            page_size=page, prefill_chunk=8, prefix_cache=True),
        "no_prefix_cache": ContinuousBatchingEngine(
            model, params, n_slots=sc["slots"], max_len=max_len,
            page_size=page, prefill_chunk=8, analyze=True),
    }
    analysis = engines["no_prefix_cache"].analysis_meta

    def _pass(eng):
        def setup():
            eng.reset()
            for prompt, glen in reqs:
                eng.submit(prompt, glen)
        return (eng.run, (), setup)

    # one warm-up inside measure_group compiles both engines' step fns
    # (including the cached engine's donor-row copy) before timing
    ms = measure_group({name: _pass(eng) for name, eng in engines.items()},
                       reps=REPEATS, warmup=1, jit=False)

    rows = []
    base = ms["no_prefix_cache"].median_s
    for name, eng in engines.items():
        s = eng.stats.summary()          # last pass (reset per repeat)
        m = ms[name]
        rows.append({
            "family": family, "arch": cfg.arch_id, "mix": "shared_prefix",
            "engine": "continuous", "cache": name,
            "slots": sc["slots"], "requests": sc["n_req"],
            "shared_prefix_len": sc["shared_len"],
            "tok_per_s": s["generated_tokens"] / m.median_s,
            "wall_s_median": m.median_s,
            "wall_s_all": [round(w, 4) for w in m.all_s],
            "generated_tokens": s["generated_tokens"],
            "prefix_hit_tokens": s["prefix_hit_tokens"],
            "prefix_hit_rate": s["prefix_hit_rate"],
            "speedup_vs_nocache": base / m.median_s,
            "model_flops": s["model_flops"],
            "model_bytes": s["model_bytes"],
            "roofline_utilization": roofline_fraction(
                s["model_flops"], s["model_bytes"], m.median_s)})
    return rows, analysis


def _paged_rows(cfg, model, params, sc: Dict, family: str = "lm", *,
                retune: bool = False) -> Tuple[List[Dict], Dict]:
    """One workload through two continuous engines — paged flash-decode
    kernel (default) vs the dense XLA gather-then-attend decode
    (``paged_kernel=False``) — as equal interleaved contenders through
    ``measure_group``.

    Both engines build with ``analyze=True``: the returned meta block
    carries each engine's trace-lint verdict on the very compiled decode
    program the rows time, plus the expected-findings contract (the
    baseline decode gathers KV pages per step → ``hot-gather``; the
    paged decode walks the page-index array inside the kernel and
    embeds via one-hot matmul → no gather at all) and the autotuned
    ``block_pages`` pick from the persistent cache."""
    page = 8
    rng = np.random.default_rng(19)
    reqs = _workload(rng, sc["n_req"], sc["prompt_band"], sc["gen_band"],
                     cfg.vocab_size)
    max_len = -(-(max(sc["prompt_band"]) + max(sc["gen_band"])) // page) * page

    engines = {
        "paged": ContinuousBatchingEngine(
            model, params, n_slots=sc["slots"], max_len=max_len,
            page_size=page, prefill_chunk=8, analyze=True,
            paged_kernel=True, retune=retune),
        "xla": ContinuousBatchingEngine(
            model, params, n_slots=sc["slots"], max_len=max_len,
            page_size=page, prefill_chunk=8, analyze=True,
            paged_kernel=False),
    }

    def _pass(eng):
        def setup():
            eng.reset()
            for prompt, glen in reqs:
                eng.submit(prompt, glen)
        return (eng.run, (), setup)

    ms = measure_group({name: _pass(eng) for name, eng in engines.items()},
                       reps=REPEATS, warmup=1, jit=False)

    kernel_label = {"paged": "paged_flash_decode", "xla": "xla_gather"}
    rows = []
    base = ms["xla"].median_s
    for name, eng in engines.items():
        s = eng.stats.summary()          # last pass (reset per repeat)
        m = ms[name]
        rows.append({
            "family": family, "arch": cfg.arch_id, "mix": "paged_vs_xla",
            "engine": "continuous", "kernel": kernel_label[name],
            "slots": sc["slots"], "requests": sc["n_req"],
            "tok_per_s": s["generated_tokens"] / m.median_s,
            "wall_s_median": m.median_s,
            "wall_s_all": [round(w, 4) for w in m.all_s],
            "generated_tokens": s["generated_tokens"],
            "speedup_vs_xla": base / m.median_s,
            "model_flops": s["model_flops"],
            "model_bytes": s["model_bytes"],
            "roofline_utilization": roofline_fraction(
                s["model_flops"], s["model_bytes"], m.median_s)})
    meta = {
        "engines": {name: eng.analysis_meta
                    for name, eng in engines.items()},
        # rules that MUST appear / MUST NOT appear on each contender's
        # decode program — ci.sh --bench-smoke enforces this split
        "expected_findings": {"paged": [], "xla": ["hot-gather"]},
        "autotune": engines["paged"].paged_meta,
    }
    return rows, meta


def _open_loop_rows(cfg, model, params, sc: Dict, family: str = "lm"
                    ) -> Tuple[List[Dict], Dict]:
    """Open-loop latency sweep: the workload arrives as a Poisson
    process at ``rate_factors`` x the calibrated closed-loop capacity,
    plus a fixed-trace replay of the mid-rate arrivals, all as equal
    interleaved contenders.  Wall timing is two-level by design: the
    outer ``measure_group`` wall is the contender's whole pass (the
    median the row reports), while TTFT/TBT/E2E come from the
    frontend's internal virtual clock (per-step ``now()`` brackets).
    The SLO every rate is judged against is derived post hoc from the
    *lowest*-rate pass — 3x its p50 TTFT and TBT — so goodput
    degradation across rates is measured against one fixed bar."""
    page = 8
    rng = np.random.default_rng(23)
    reqs = synthetic_requests(sc["n_req"], sc["prompt_band"],
                              sc["gen_band"], cfg.vocab_size, seed=23)
    extra = stub_context(cfg, rng)
    max_len = -(-(max(sc["prompt_band"]) + max(sc["gen_band"])) // page) * page
    eng = ContinuousBatchingEngine(
        model, params, n_slots=sc["slots"], max_len=max_len,
        page_size=page, prefill_chunk=8)
    front = OpenLoopFrontend(eng)            # measurement (wall) clock

    def _closed_setup():
        eng.reset()
        for prompt, glen in reqs:
            eng.submit(prompt, glen, extra=extra)

    # calibrate the knee: closed-loop drain throughput in requests/s is
    # the service capacity the arrival rates bracket (warmup compiles
    # every step shape before any timed pass)
    mcap = perf_measure(eng.run, reps=REPEATS, warmup=1, jit=False,
                        setup=_closed_setup)
    capacity_req_s = sc["n_req"] / mcap.median_s

    factors = tuple(sc["rate_factors"])
    names = [f"poisson_{f:g}x" for f in factors]
    arrs = {name: poisson_arrivals(reqs, f * capacity_req_s, seed=29,
                                   extra=extra)
            for name, f in zip(names, factors)}
    # fixed-trace contender: the mid-rate arrivals serialized to the
    # repro.serve.trace schema and replayed — pins a reproducible
    # workload and exercises the replay path end to end (per-request
    # extra context rides alongside; the trace itself stays pure JSON)
    mid = names[len(names) // 2]
    arrs["trace_replay"] = trace_arrivals(trace_payload(arrs[mid]),
                                          extra=extra)

    def _pass(arr):
        def setup():
            eng.reset()
        return (front.run, (arr,), setup)

    ms = measure_group({name: _pass(arr) for name, arr in arrs.items()},
                       reps=REPEATS, warmup=1, jit=False)

    # one SLO for every contender, from the uncontended baseline
    lowest = names[0]
    lat0 = ms[lowest].result.summary()
    slo = SLO(ttft_s=max(3 * lat0["ttft_s"]["p50"], 1e-9),
              tbt_s=max(3 * lat0["tbt_s"]["p50"], 1e-9))

    factor_of = dict(zip(names, factors))
    factor_of["trace_replay"] = factors[len(names) // 2]
    rows = []
    for name in arrs:
        m = ms[name]
        res = m.result                   # last repeat's OpenLoopResult
        lat = res.summary(slo=slo)
        s = res.engine_summary
        rows.append({
            "family": family, "arch": cfg.arch_id, "mix": "open_loop",
            "engine": "continuous",
            "arrival": ("trace" if name == "trace_replay" else "poisson"),
            "rate_req_s": factor_of[name] * capacity_req_s,
            "rate_factor": factor_of[name],
            "slots": sc["slots"], "requests": sc["n_req"],
            "wall_s_median": m.median_s,
            "wall_s_all": [round(w, 4) for w in m.all_s],
            "generated_tokens": s["generated_tokens"],
            "tok_per_s": (s["generated_tokens"] / m.median_s
                          if m.median_s > 0 else 0.0),
            # flattened convenience columns; the full surface is
            # ``latency`` (schema-validated by repro.perf --validate)
            "ttft_p50_s": lat["ttft_s"]["p50"],
            "ttft_p99_s": lat["ttft_s"]["p99"],
            "tbt_p99_s": lat["tbt_s"]["p99"],
            "slo_attainment": lat["slo"]["attainment"],
            "goodput_tok_s": lat["goodput_tok_s"],
            "latency": lat,
            "model_flops": s["model_flops"],
            "model_bytes": s["model_bytes"],
            "roofline_utilization": roofline_fraction(
                s["model_flops"], s["model_bytes"], m.median_s)})
    meta = {
        "capacity_req_s": capacity_req_s,
        "closed_loop_wall_s": mcap.median_s,
        "clock": "wall",
        "slo": {"ttft_s": slo.ttft_s, "tbt_s": slo.tbt_s,
                "derived": f"3x p50 of the {lowest} pass"},
    }
    return rows, meta


def _spec_rows(cfg, model, params, sc: Dict, family: str = "lm"
               ) -> Tuple[List[Dict], Dict]:
    """Two prompt mixes (repetitive / random) through two continuous
    engines — n-gram draft-verify speculation on vs off — as equal
    interleaved contenders through ``measure_group``.

    Both engines decode the same greedy workload, so their token output
    is identical (the speculative parity contract, pinned by
    tests/test_serve_spec.py); the rows compare pure wall.  accept_rate
    comes from the spec engine's stats (accepted draft tokens / drafted
    tokens over the last timed pass)."""
    page = 8
    rng = np.random.default_rng(31)
    # cross-context families (audio/vlm) need their stub context at
    # submit; one shared context keeps the comparison about decode wall
    extra = stub_context(cfg, rng)
    motif = rng.integers(1, cfg.vocab_size, size=sc["motif_len"])
    mixes: Dict[str, List] = {}
    for mix in ("repetitive", "random"):
        reqs = []
        for _ in range(sc["n_req"]):
            plen = int(rng.integers(*sc["prompt_band"]))
            if mix == "repetitive":
                prompt = np.tile(motif, -(-plen // len(motif)))[:plen]
            else:
                prompt = rng.integers(1, cfg.vocab_size, size=plen)
            reqs.append((prompt.astype(np.int64),
                         int(rng.integers(*sc["gen_band"]))))
        mixes[mix] = reqs
    max_len = -(-(max(sc["prompt_band"]) + max(sc["gen_band"])) // page) * page

    engines = {
        "spec": ContinuousBatchingEngine(
            model, params, n_slots=sc["slots"], max_len=max_len,
            page_size=page, prefill_chunk=8,
            spec_decode=True, spec_k=sc["spec_k"]),
        "nonspec": ContinuousBatchingEngine(
            model, params, n_slots=sc["slots"], max_len=max_len,
            page_size=page, prefill_chunk=8),
    }

    rows: List[Dict] = []
    meta: Dict = {"spec_k": sc["spec_k"], "accept_rate": {}}
    for mix, reqs in mixes.items():
        def _pass(eng, reqs=reqs):
            def setup():
                eng.reset()
                for prompt, glen in reqs:
                    eng.submit(prompt, glen, extra=extra)
            return (eng.run, (), setup)

        ms = measure_group(
            {name: _pass(eng) for name, eng in engines.items()},
            reps=REPEATS, warmup=1, jit=False)

        base = ms["nonspec"].median_s
        for name, eng in engines.items():
            s = eng.stats.summary()      # last pass (reset per repeat)
            m = ms[name]
            rows.append({
                "family": family, "arch": cfg.arch_id,
                "mix": f"spec_{mix}", "engine": "continuous",
                "speculative": name == "spec",
                "spec_k": sc["spec_k"] if name == "spec" else 0,
                "slots": sc["slots"], "requests": sc["n_req"],
                "tok_per_s": s["generated_tokens"] / m.median_s,
                "wall_s_median": m.median_s,
                "wall_s_all": [round(w, 4) for w in m.all_s],
                "generated_tokens": s["generated_tokens"],
                "accept_rate": s["accept_rate"],
                "drafted_tokens": s["drafted_tokens"],
                "accepted_draft_tokens": s["accepted_draft_tokens"],
                "speedup_vs_nonspec": base / m.median_s,
                "model_flops": s["model_flops"],
                "model_bytes": s["model_bytes"],
                "roofline_utilization": roofline_fraction(
                    s["model_flops"], s["model_bytes"], m.median_s)})
        meta["accept_rate"][f"{family}/{mix}"] = (
            engines["spec"].stats.summary()["accept_rate"])
    return rows, meta


def _sharded_mesh(count: int, sp_kv: bool):
    if count == 1:
        return None                      # the strict single-device path
    if sp_kv:
        return make_mesh((count, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    return make_mesh((count,), ("data",), axis_types=(AxisType.Auto,))


def _sharded_rows(cfg, model, params, sc: Dict, family: str,
                  sp_kv: bool = False) -> tuple[List[Dict], Dict]:
    """One workload through mesh-sharded continuous engines at every
    runnable shard count, as equal interleaved contenders; returns the
    rows plus each engine's resolved-layout record for the Report meta
    (rules + forced-replication decisions — the layout that actually
    ran)."""
    page = 8
    rng = np.random.default_rng(17)
    reqs = _workload(rng, sc["n_req"], sc["prompt_band"], sc["gen_band"],
                     cfg.vocab_size)
    # cross-context families: one shared stub context for the workload
    # (per-request contexts would only change the install traffic)
    extra = stub_context(cfg, rng)
    max_len = -(-(max(sc["prompt_band"]) + max(sc["gen_band"])) // page) * page
    n_dev = len(jax.devices())

    def devices_needed(c):
        # shards=1 is the strict single-device path (mesh=None, sp_kv
        # off) — it never needs more than one device
        return 1 if c == 1 else c * (2 if sp_kv else 1)

    counts = [c for c in SHARD_COUNTS
              if sc["slots"] % c == 0 and devices_needed(c) <= n_dev]
    dropped = [c for c in SHARD_COUNTS if c not in counts]
    if dropped:
        print(f"[serve_bench] sharded: skipping shard counts {dropped} — "
              f"{n_dev} device(s) visible; fake more with "
              "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    engines = {
        c: ContinuousBatchingEngine(
            model, params, n_slots=sc["slots"], max_len=max_len,
            page_size=page, prefill_chunk=8,
            mesh=_sharded_mesh(c, sp_kv), sp_kv=sp_kv and c > 1)
        for c in counts}

    def _pass(eng):
        def setup():
            eng.reset()
            for prompt, glen in reqs:
                eng.submit(prompt, glen, extra=extra)
        return (eng.run, (), setup)

    ms = measure_group({f"shards={c}": _pass(e) for c, e in engines.items()},
                       reps=REPEATS, warmup=1, jit=False)

    rows, layouts = [], {}
    base = ms["shards=1"].median_s if 1 in engines else None
    for c, eng in engines.items():
        m = ms[f"shards={c}"]
        s = eng.stats.summary()          # last pass (reset per repeat)
        rows.append({
            "family": family, "arch": cfg.arch_id, "mix": "sharded",
            "engine": "continuous", "shards": c, "slots": sc["slots"],
            "requests": sc["n_req"],
            "tok_per_s": s["generated_tokens"] / m.median_s,
            "wall_s_median": m.median_s,
            "wall_s_all": [round(w, 4) for w in m.all_s],
            "generated_tokens": s["generated_tokens"],
            "model_flops": s["model_flops"],
            "model_bytes": s["model_bytes"],
            "roofline_utilization": roofline_fraction(
                s["model_flops"], s["model_bytes"], m.median_s),
            "speedup_vs_1shard": (base / m.median_s
                                  if base is not None else 1.0)})
        if eng.sharding_meta is not None:
            layouts[f"{family}/shards={c}"] = eng.sharding_meta
    return rows, layouts


def _mix_rows(cfg, model, params, mixes, family: str) -> List[Dict]:
    rows = []
    for name, slots, p_band, g_band, n_req in mixes:
        rng = np.random.default_rng(7)
        reqs = _workload(rng, n_req, p_band, g_band, cfg.vocab_size)
        page = 8
        max_len = -(-(max(p_band) + max(g_band)) // page) * page
        st, ct = _run_pair(model, params, reqs, slots, max_len,
                           page_size=page)
        for engine_name, r in (("static", st), ("continuous", ct)):
            rows.append({"family": family, "arch": cfg.arch_id,
                         "mix": name, "engine": engine_name,
                         "slots": slots, "requests": n_req,
                         "speedup_vs_static": (r["tok_per_s"]
                                               / st["tok_per_s"]), **r})
    return rows


def _fingerprint_digest(analysis: Optional[Dict]) -> Optional[Dict]:
    """Compact per-program digest of the compile-drift fingerprints the
    analysis block carries (``meta["fingerprints"]``): just the
    drift-relevant axes — gathers, donation aliasing, counter verdict,
    firing rules — so a reader (or the --bench-smoke gate) can spot a
    regression without unpacking the full op histograms."""
    if not analysis or not analysis.get("programs"):
        return None
    out: Dict[str, Dict] = {}
    for label, prog in analysis["programs"].items():
        fp = prog.get("fingerprint") or {}
        out[label] = {
            "version": fp.get("version"),
            "gather_ops": fp.get("gather_ops"),
            "alias_pairs": fp.get("alias_pairs"),
            "donated": fp.get("donated"),
            "counters_verdict": (fp.get("counters") or {}).get("verdict"),
            "finding_rules": fp.get("finding_rules"),
        }
    return out


def run(measure: bool = True,
        families: Optional[List[str]] = None,
        prefix_only: bool = False,
        sharded: bool = False,
        sp_kv: bool = False,
        retune: bool = False,
        open_loop: bool = False,
        speculative: bool = False) -> List[Dict]:
    rows: List[Dict] = []
    smoke = bool(os.environ.get("REPRO_BENCH_SMOKE"))
    if speculative:
        # its own artifact (serve_bench_speculative.json): n-gram
        # draft-verify speculation vs the plain decode loop per family,
        # on a repetitive and a random prompt mix
        sc = SPEC_SCENARIO_SMOKE if smoke else SPEC_SCENARIO
        # default: every family (the per-family accept-rate x tok/s
        # surface); the CI smoke pins just audio, the draft-friendliest
        # family (its decoder falls into short greedy cycles the
        # prompt-lookup drafter locks onto), where the repetitive-mix
        # ordering assertion must hold
        fams = families or (["audio"] if smoke else list(FAMILY_ARCHS))
        if "all" in fams:
            fams = list(FAMILY_ARCHS)
        unknown = sorted(set(fams) - set(FAMILY_ARCHS))
        if unknown:
            raise SystemExit(
                f"unknown families {unknown}; choose from "
                f"{sorted(FAMILY_ARCHS)} or 'all'")
        per_family_meta: Dict[str, Dict] = {}
        for fam in fams:
            cfg = reduced_config(FAMILY_ARCHS[fam])
            model = build_model(cfg)
            params = model.init_params(jax.random.key(0))
            r, smeta = _spec_rows(cfg, model, params, sc, fam)
            rows += r
            per_family_meta[fam] = smeta
        common.save_result(
            "serve_bench_speculative", rows,
            meta={"reduced": True, "repeats": REPEATS,
                  "statistic": "median", "smoke": smoke, "families": fams,
                  "speculative": per_family_meta})
        common.print_table(
            "speculative decoding: n-gram draft-verify vs plain decode "
            "(continuous engine, median of interleaved repeats)", rows,
            ["family", "mix", "speculative", "generated_tokens",
             "accept_rate", "tok_per_s", "speedup_vs_nonspec"],
            widths={"family": 7, "mix": 16, "speculative": 11,
                    "speedup_vs_nonspec": 19})
        print("-> both contenders emit identical greedy tokens (the "
              "speculative parity contract); accept_rate = accepted "
              "draft tokens / drafted.  Repetitive prompts feed the "
              "prompt-lookup drafter from step one; on random prompts "
              "it can only lock onto the model's own greedy cycles.")
        return rows
    if open_loop:
        # its own artifact (serve_bench_open_loop.json): latency rows
        # carry the new schema-validated ``latency`` block, and the
        # classic closed-loop serve_bench.json stays unchanged
        sc = OPEN_LOOP_SCENARIO_SMOKE if smoke else OPEN_LOOP_SCENARIO
        fams = families or ["lm"]
        if "all" in fams:
            fams = list(FAMILY_ARCHS)
        unknown = sorted(set(fams) - set(FAMILY_ARCHS))
        if unknown:
            raise SystemExit(
                f"unknown families {unknown}; choose from "
                f"{sorted(FAMILY_ARCHS)} or 'all'")
        per_family_meta: Dict[str, Dict] = {}
        for fam in fams:
            cfg = reduced_config(FAMILY_ARCHS[fam])
            model = build_model(cfg)
            params = model.init_params(jax.random.key(0))
            r, ometa = _open_loop_rows(cfg, model, params, sc, fam)
            rows += r
            per_family_meta[fam] = ometa
        common.save_result(
            "serve_bench_open_loop", rows,
            meta={"reduced": True, "repeats": REPEATS,
                  "statistic": "median", "smoke": smoke, "families": fams,
                  "open_loop": per_family_meta})
        common.print_table(
            "open-loop serving: Poisson rate sweep around the "
            "closed-loop knee (continuous engine, median of "
            "interleaved repeats)", rows,
            ["family", "arrival", "rate_factor", "ttft_p50_s",
             "ttft_p99_s", "tbt_p99_s", "slo_attainment",
             "goodput_tok_s"],
            widths={"family": 7, "arrival": 8, "rate_factor": 12,
                    "slo_attainment": 15})
        print("-> TTFT/TBT come from the frontend's virtual clock "
              "(per-step now() brackets); the SLO every rate is judged "
              "against is 3x the lowest rate's p50, so goodput shows "
              "how latency degrades as arrivals pass the knee.")
        return rows
    if sharded:
        # its own artifact: the classic serve_bench.json stays a pure
        # single-device report, and the CI smoke validates both
        sc = SHARDED_SCENARIO_SMOKE if smoke else SHARDED_SCENARIO
        fams = families or ["lm"]
        if "all" in fams:
            fams = list(FAMILY_ARCHS)
        unknown = sorted(set(fams) - set(FAMILY_ARCHS))
        if unknown:
            raise SystemExit(
                f"unknown families {unknown}; choose from "
                f"{sorted(FAMILY_ARCHS)} or 'all'")
        layouts: Dict[str, Dict] = {}
        for fam in fams:
            cfg = reduced_config(FAMILY_ARCHS[fam])
            model = build_model(cfg)
            params = model.init_params(jax.random.key(0))
            r, lay = _sharded_rows(cfg, model, params, sc, fam, sp_kv=sp_kv)
            rows += r
            layouts.update(lay)
        common.save_result(
            "serve_bench_sharded", rows,
            meta={"reduced": True, "repeats": REPEATS,
                  "statistic": "median", "smoke": smoke, "families": fams,
                  "sp_kv": sp_kv, "sharding": layouts})
        common.print_table(
            "sharded serving: slot shards over the mesh (continuous "
            "engine, median of interleaved repeats)", rows,
            ["family", "shards", "generated_tokens", "tok_per_s",
             "speedup_vs_1shard", "roofline_utilization"],
            widths={"family": 7, "speedup_vs_1shard": 18,
                    "roofline_utilization": 21})
        print("-> host-CPU walls over faked devices measure sharding "
              "overhead, not speedup — on real multi-chip hardware the "
              "slot shards decode in parallel; Report meta records each "
              "engine's resolved layout + forced replications.")
        return rows
    paged_meta: Optional[Dict] = None
    if smoke or prefix_only:
        # CI smoke (scripts/ci.sh --bench-smoke) / --prefix-only: the
        # shared-prefix scenario at tiny shapes, through the same Report
        # write path so the schema gate judges a real artifact; the smoke
        # additionally races the paged kernel vs the XLA-gather decode so
        # the gate can enforce the expected-findings split
        cfg = reduced_config(ARCH)
        model = build_model(cfg)
        params = model.init_params(jax.random.key(0))
        rows, analysis = _prefix_rows(cfg, model, params,
                                      PREFIX_SCENARIO_SMOKE if smoke
                                      else PREFIX_SCENARIO)
        if smoke:
            paged_rows, paged_meta = _paged_rows(
                cfg, model, params, PAGED_SCENARIO_SMOKE, retune=retune)
            rows += paged_rows
    elif families:
        analysis = None                  # mix-only rows, no traced engine
        if "all" in families:
            families = list(FAMILY_ARCHS)
        unknown = sorted(set(families) - set(FAMILY_ARCHS))
        if unknown:
            raise SystemExit(
                f"unknown families {unknown}; choose from "
                f"{sorted(FAMILY_ARCHS)} or 'all'")
        for fam in families:
            cfg = reduced_config(FAMILY_ARCHS[fam])
            model = build_model(cfg)
            params = model.init_params(jax.random.key(0))
            rows += _mix_rows(cfg, model, params, [HIGH_VARIANCE_MIX], fam)
    else:
        cfg = reduced_config(ARCH)
        model = build_model(cfg)
        params = model.init_params(jax.random.key(0))
        rows += _mix_rows(cfg, model, params, MIXES, "lm")
        prefix_rows, analysis = _prefix_rows(cfg, model, params,
                                             PREFIX_SCENARIO)
        rows += prefix_rows
        paged_rows, paged_meta = _paged_rows(cfg, model, params,
                                             PAGED_SCENARIO, retune=retune)
        rows += paged_rows
    common.save_result("serve_bench", rows,
                       meta={"reduced": True, "repeats": REPEATS,
                             "statistic": "median", "smoke": smoke,
                             "families": families or ["lm"],
                             "analysis": analysis,
                             "fingerprints": _fingerprint_digest(analysis),
                             "paged": paged_meta})
    classic = [r for r in rows
               if r["mix"] not in ("shared_prefix", "paged_vs_xla")]
    prefix = [r for r in rows if r["mix"] == "shared_prefix"]
    paged = [r for r in rows if r["mix"] == "paged_vs_xla"]
    if classic:
        common.print_table(
            "serving throughput: continuous batching vs static (reduced, "
            "median of interleaved repeats)", classic,
            ["family", "mix", "engine", "generated_tokens", "tok_per_s",
             "speedup_vs_static", "mean_occupancy", "roofline_utilization"],
            widths={"family": 7, "mix": 14, "engine": 11,
                    "roofline_utilization": 21})
        print("-> roofline_utilization = modeled bound time (costmodel "
              "flops/bytes vs the TPU-v5e ceiling) / measured host wall; "
              "absolute values are small on this host — compare across "
              "families and engines, not against 1.0.")
    if prefix:
        common.print_table(
            "shared-prefix workload: prefix cache on vs off (continuous "
            "engine, median of interleaved repeats)", prefix,
            ["cache", "generated_tokens", "prefix_hit_tokens",
             "prefix_hit_rate", "tok_per_s", "speedup_vs_nocache"],
            widths={"cache": 16, "prefix_hit_tokens": 17,
                    "speedup_vs_nocache": 19})
        print("-> prefix_hit_rate = prompt tokens served by donor-row "
              "copies / all prompt tokens; prefill compute skipped "
              "entirely for hit tokens (the paper's weakest RVV path).")
    if paged:
        common.print_table(
            "paged flash-decode kernel vs XLA gather decode (continuous "
            "engine, median of interleaved repeats)", paged,
            ["kernel", "generated_tokens", "tok_per_s", "speedup_vs_xla",
             "roofline_utilization"],
            widths={"kernel": 18, "speedup_vs_xla": 15,
                    "roofline_utilization": 21})
        print("-> both contenders decode the same page table; the paged "
              "kernel walks the page-index array inside the attention "
              "kernel (no per-step KV gather, embed via one-hot matmul) "
              "— Report meta records each decode program's trace-lint "
              "findings and the autotuned block_pages pick.")
    return rows


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--families", default=None,
                    help="'all' or comma list of "
                         f"{sorted(FAMILY_ARCHS)} — runs the "
                         "high-variance mix per family")
    ap.add_argument("--prefix-only", action="store_true",
                    help="run only the shared-prefix scenario "
                         "(full shapes; REPRO_BENCH_SMOKE=1 for tiny)")
    ap.add_argument("--sharded", action="store_true",
                    help="run only the sharded scenario: 1/2/4 slot "
                         "shards interleaved (writes "
                         "serve_bench_sharded.json; REPRO_BENCH_SMOKE=1 "
                         "for tiny shapes)")
    ap.add_argument("--sp-kv", action="store_true",
                    help="sharded scenario uses (data x model) meshes "
                         "and shards the KV sequence axis too")
    ap.add_argument("--retune", action="store_true",
                    help="force re-measurement of the paged-kernel "
                         "block_pages sweep (ignore "
                         "benchmarks/results/autotune_cache.json)")
    ap.add_argument("--open-loop", action="store_true",
                    help="run only the open-loop latency scenario: "
                         "Poisson rate sweep + trace replay (writes "
                         "serve_bench_open_loop.json; REPRO_BENCH_SMOKE=1 "
                         "for tiny shapes)")
    ap.add_argument("--speculative", action="store_true",
                    help="run only the speculative-decoding scenario: "
                         "n-gram draft-verify vs plain decode on "
                         "repetitive + random prompt mixes (writes "
                         "serve_bench_speculative.json; "
                         "REPRO_BENCH_SMOKE=1 for tiny shapes)")
    args = ap.parse_args()
    run(families=args.families.split(",") if args.families else None,
        prefix_only=args.prefix_only, sharded=args.sharded,
        sp_kv=args.sp_kv, retune=args.retune, open_loop=args.open_loop,
        speculative=args.speculative)


if __name__ == "__main__":
    main()
