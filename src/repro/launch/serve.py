"""Serving launcher: continuous batching with the paged decode state.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
      --reduced --slots 4 --requests 8 --prompt-len 32 --gen-len 32 [--int8]

Every family (lm / ssm / hybrid / vlm / audio) runs the continuous-
batching engine via the DecodeState protocol; ``--static`` selects the
fixed-batch StaticBatchEngine baseline instead.

``--mesh 2`` / ``--mesh 2x2`` / ``--mesh 2x16x16`` serves sharded: the
decode slot axis lays out over ("pod", "data"), tensor parallelism over
"model", and ``--sp-kv`` additionally shards the KV-cache sequence axis
(flash-decoding).  On a CPU host fake the devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

``--open-loop`` routes the workload through the open-loop front end
(``repro.serve.OpenLoopFrontend``): requests *arrive* on a clock
instead of being queued up front, and the run prints TTFT/TBT/E2E
percentiles, queue depth, and goodput under a TTFT+TBT SLO.
``--rate`` sets the arrival rate in requests/s (0 = closed-loop
arrivals through the frontend), ``--arrival poisson|gamma|trace``
picks the process (``--cv`` tunes gamma burstiness), ``--trace``
replays a ``repro.serve.trace`` JSON workload file, ``--slo-ttft`` /
``--slo-tbt`` set the SLO bounds, and
``--chunk-policy stall_free --tbt-target`` makes the scheduler's
prefill chunk a per-step decision tuned to the TBT target.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, reduced_config
from repro.core.compile_cache import use_compile_cache
from repro.launch.mesh import parse_mesh
from repro.models import build_model
from repro.models.decode_state import stub_context
from repro.models.quant import quantize_params
from repro.perf.measure import now
from repro.serve import ContinuousBatchingEngine, StaticBatchEngine


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--slots", "--batch", dest="slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=0,
                    help="queued requests (default: 2x slots)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--int8", action="store_true",
                    help="weight-only int8 serving")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="page-table-keyed prefix caching: shared "
                         "page-aligned prompt prefixes are copied from "
                         "pooled donor rows instead of re-prefilled "
                         "(token-addressable families only)")
    ap.add_argument("--prefix-pool", type=int, default=8,
                    help="max pooled prefix entries (LRU bound)")
    ap.add_argument("--static", action="store_true",
                    help="fixed-batch StaticBatchEngine baseline")
    ap.add_argument("--mesh", default=None,
                    help="device mesh for sharded serving: N (data), "
                         "NxM (data x model) or NxMxK (pod x data x "
                         "model); decode slots shard over (pod, data)")
    ap.add_argument("--sp-kv", action="store_true",
                    help="also shard the KV-cache sequence axis over "
                         "'model' (sequence-parallel flash-decoding); "
                         "needs a mesh with a model axis")
    ap.add_argument("--open-loop", action="store_true",
                    help="serve through the open-loop front end: "
                         "requests arrive on a clock; prints TTFT/TBT/"
                         "E2E percentiles and goodput under the SLO")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop arrival rate in requests/s "
                         "(0 = all requests arrive at t=0)")
    ap.add_argument("--arrival", default="poisson",
                    choices=("poisson", "gamma", "trace"),
                    help="open-loop arrival process (gamma: see --cv; "
                         "trace: see --trace)")
    ap.add_argument("--cv", type=float, default=2.0,
                    help="gamma arrivals: inter-arrival coefficient of "
                         "variation (>1 = burstier than Poisson)")
    ap.add_argument("--trace", default=None,
                    help="replay a repro.serve.trace JSON workload file "
                         "(implies --arrival trace)")
    ap.add_argument("--slo-ttft", type=float, default=None,
                    help="SLO: max seconds to first token (default: "
                         "3x the run's p50 TTFT)")
    ap.add_argument("--slo-tbt", type=float, default=None,
                    help="SLO: max seconds between tokens (default: "
                         "3x the run's p50 TBT)")
    ap.add_argument("--chunk-policy", default="fixed",
                    choices=("fixed", "stall_free"),
                    help="prefill chunking: fixed constant-width chunks "
                         "or per-step stall-free widths tuned to "
                         "--tbt-target")
    ap.add_argument("--tbt-target", type=float, default=None,
                    help="stall_free chunk policy: the decode "
                         "time-between-tokens bound (seconds) chunks "
                         "are sized against")
    ap.add_argument("--speculative", action="store_true",
                    help="n-gram draft-verify speculative decoding: "
                         "verify up to --spec-k drafted tokens per row "
                         "per step (greedy rows only; identical tokens, "
                         "fewer steps)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens verified per row per step")
    ap.add_argument("--record-trace", default=None, metavar="PATH",
                    help="open-loop only: write the run's completed "
                         "arrivals as a replayable repro.serve.trace "
                         "JSON workload file (replay with --trace PATH)")
    args = ap.parse_args()
    if args.record_trace and not args.open_loop:
        raise SystemExit("--record-trace needs --open-loop (it records "
                         "the front end's completed arrivals)")

    cfg = (reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    if args.int8:
        params = quantize_params(params)
        print("[serve] int8 weight-only quantization enabled")

    n_req = args.requests or 2 * args.slots
    max_len = args.prompt_len + args.gen_len + 8
    rng = np.random.default_rng(1)

    if args.static:
        print(f"[serve] family {cfg.family!r}: StaticBatchEngine baseline")
        engine = StaticBatchEngine(model, params, max_len=max_len,
                                   batch=args.slots,
                                   sample_temperature=args.temperature)
        prompt = jax.random.randint(jax.random.key(1),
                                    (args.slots, args.prompt_len), 1,
                                    cfg.vocab_size)
        extra = stub_context(cfg, rng, batch=args.slots)
        if extra is not None:
            extra = {k: jnp.asarray(v) for k, v in extra.items()}
        t0 = now()
        out = engine.generate(prompt, n_steps=args.gen_len, extra=extra)
        jax.block_until_ready(out)
        dt = now() - t0
        print(f"[serve] {args.arch} batch={args.slots}: "
              f"{args.gen_len * args.slots / dt:.1f} tok/s aggregate "
              f"(incl. compile); sample: {out[0, :12].tolist()}")
        return

    page = args.page_size
    max_len = -(-max_len // page) * page                  # round up to pages
    mesh = parse_mesh(args.mesh)
    if args.sp_kv and (mesh is None or "model" not in mesh.shape):
        raise SystemExit("--sp-kv needs --mesh with a model axis "
                         "(e.g. --mesh 2x2)")
    if args.chunk_policy == "stall_free" and not args.tbt_target:
        raise SystemExit("--chunk-policy stall_free needs --tbt-target "
                         "(seconds between decode tokens)")
    engine = ContinuousBatchingEngine(
        model, params, n_slots=args.slots, max_len=max_len,
        page_size=page, prefill_chunk=args.prefill_chunk,
        chunk_policy=args.chunk_policy, tbt_target_s=args.tbt_target,
        prefix_cache=args.prefix_cache, prefix_pool=args.prefix_pool,
        mesh=mesh, sp_kv=args.sp_kv,
        spec_decode=args.speculative, spec_k=args.spec_k)
    if args.prefix_cache and not engine.prefix_cache:
        print(f"[serve] family {cfg.family!r} has non-token-addressable "
              "(recurrent) decode state; prefix cache disabled")
    if mesh is not None:
        sm = engine.sharding_meta
        print(f"[serve] mesh {sm['mesh']}: {engine.n_shards} slot "
              f"shard(s), sp_kv={sm['sp_kv']}"
              + (f"; forced replication: {sm['forced_replication']}"
                 if sm["forced_replication"] else ""))
    if args.open_loop:
        from repro.serve import (SLO, OpenLoopFrontend,
                                 closed_loop_arrivals, gamma_arrivals,
                                 poisson_arrivals, trace_arrivals)
        extra = stub_context(cfg, rng)
        if args.trace or args.arrival == "trace":
            if not args.trace:
                raise SystemExit("--arrival trace needs --trace FILE")
            arr = trace_arrivals(args.trace, vocab_size=cfg.vocab_size,
                                 extra=extra)
            label = f"trace {args.trace}"
        else:
            items = []
            for _ in range(n_req):
                plen = int(rng.integers(max(1, args.prompt_len // 2),
                                        args.prompt_len + 1))
                items.append((rng.integers(1, cfg.vocab_size, size=plen),
                              args.gen_len))
            if args.rate <= 0:
                arr = closed_loop_arrivals(
                    items, temperature=args.temperature, extra=extra)
                label = "closed-loop (all at t=0)"
            elif args.arrival == "gamma":
                arr = gamma_arrivals(items, args.rate, cv=args.cv, seed=2,
                                     temperature=args.temperature,
                                     extra=extra)
                label = f"gamma rate={args.rate}/s cv={args.cv}"
            else:
                arr = poisson_arrivals(items, args.rate, seed=2,
                                       temperature=args.temperature,
                                       extra=extra)
                label = f"poisson rate={args.rate}/s"
        res = OpenLoopFrontend(engine).run(arr)
        if args.record_trace:
            from repro.serve import save_trace
            save_trace(args.record_trace, res.completed_arrivals)
            print(f"[serve] recorded {len(res.completed_arrivals)} "
                  f"completed arrival(s) -> {args.record_trace} "
                  f"(replay with --arrival trace --trace "
                  f"{args.record_trace})")
        lat = res.summary()
        ttft = (args.slo_ttft if args.slo_ttft is not None
                else 3 * lat["ttft_s"]["p50"])
        tbt = (args.slo_tbt if args.slo_tbt is not None
               else 3 * lat["tbt_s"]["p50"])
        slo = SLO(ttft_s=ttft, tbt_s=tbt) if ttft > 0 and tbt > 0 else None
        if slo is not None:
            lat = res.summary(slo=slo)
        print(f"[serve] open-loop {args.arch} ({cfg.family}) "
              f"slots={args.slots} requests={lat['requests']} "
              f"completed={lat['completed']}: {label}")
        for key, name in (("ttft_s", "TTFT"), ("tbt_s", "TBT"),
                          ("e2e_s", "E2E")):
            d = lat[key]
            print(f"[serve]   {name}: p50={d['p50'] * 1e3:.2f}ms "
                  f"p90={d['p90'] * 1e3:.2f}ms "
                  f"p99={d['p99'] * 1e3:.2f}ms (n={d['n']})")
        q = lat["queue_depth"]
        print(f"[serve]   queue depth: mean={q['mean']:.2f} "
              f"max={q['max']}; makespan={lat['makespan_s'] * 1e3:.1f}ms")
        if slo is not None:
            print(f"[serve]   SLO(ttft<={slo.ttft_s * 1e3:.1f}ms, "
                  f"tbt<={slo.tbt_s * 1e3:.1f}ms): "
                  f"attainment={lat['slo']['attainment']:.2f} "
                  f"goodput={lat['goodput_tok_s']:.1f} tok/s")
        if args.speculative:
            es = res.engine_summary
            print(f"[serve]   speculative k={args.spec_k}: "
                  f"accept_rate={es['accept_rate']:.2f} "
                  f"({es['accepted_draft_tokens']}/{es['drafted_tokens']}"
                  f" draft tokens accepted)")
        if args.chunk_policy == "stall_free":
            print(f"[serve]   stall-free chunks: last width "
                  f"{engine.sched.last_chunk_width} "
                  f"(base {args.prefill_chunk})")
        return
    for _ in range(n_req):
        plen = int(rng.integers(max(1, args.prompt_len // 2),
                                args.prompt_len + 1))
        prompt = rng.integers(1, cfg.vocab_size, size=plen)
        engine.submit(prompt, args.gen_len,
                      temperature=args.temperature,
                      extra=stub_context(cfg, rng))
    t0 = now()
    engine.run()
    dt = now() - t0
    s = engine.stats.summary()
    print(f"[serve] {args.arch} ({cfg.family}) slots={args.slots} "
          f"requests={n_req}: "
          f"{s['generated_tokens'] / dt:.1f} tok/s aggregate "
          f"(incl. compile); steps={s['steps']} "
          f"p50={s['step_ms_p50']:.1f}ms "
          f"occupancy={s['mean_occupancy']:.2f}")
    if engine.prefix_cache:
        print(f"[serve] prefix cache: {s['prefix_hit_tokens']} prompt "
              f"tokens served from pooled pages "
              f"(hit rate {s['prefix_hit_rate']:.2f})")
    if args.speculative:
        print(f"[serve] speculative k={args.spec_k}: "
              f"accept_rate={s['accept_rate']:.2f} "
              f"drafted={s['drafted_tokens']} "
              f"accepted={s['accepted_draft_tokens']}")
    first = engine.requests()[0]
    print(f"[serve] sample rid={first.rid}: "
          f"{first.generated[:12]}")


if __name__ == "__main__":
    main()
