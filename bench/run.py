#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <config>.<mix> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``, which
names its reference) and a traffic mix (``bench/traffic/<mix>.json``,
which names its generator); BENCHMARK.json lists the cells and their
metrics, and each metric is read by ``bench/metrics/<metric>.py``.  The run makes its weights and requests
from the seed, warms up every program the window drives, serves for
``--seconds`` through the program's engine, checks the served tokens
against the float32 reference, and prints one JSON object as the last
line of standard output.  With ``--trace 1`` it also records a profiler
trace of a few seconds in the middle of the window and reports the
per-layer metrics in place of the end-to-end ones.

It exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))
# JAX's persistent compile cache lives at a fixed path in the checkout
# unless the environment names one
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
# the TPU runtime's logs stay in the checkout too
if "TPU_LOG_DIR" not in os.environ:
    os.makedirs(ROOT / "bench_out" / "tpu_logs", exist_ok=True)
    os.environ["TPU_LOG_DIR"] = str(ROOT / "bench_out" / "tpu_logs")

TRACE_SECONDS = 4.0       # traced slice, centred in the window


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def check_device(chips: int):
    """The devices JAX found; exits (no result) unless they are TPUs
    and at least ``chips`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found platform "
                         f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    return devs


class CompileCounter:
    """Counts JAX tracings and backend compilations while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, _secs, **_kw):
        if self.on and event in self.EVENTS:
            self.n += 1


class GcWatch:
    """Python's collections of the oldest generation while ``on``, and
    the longest of them: each stops the host, and with it the device,
    which waits for the next step."""

    def __init__(self):
        self.on, self.pauses, self._t = False, [], 0.0
        gc.callbacks.append(self._hear)

    def _hear(self, phase, info):
        if not self.on or info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t)


def open_window() -> None:
    """The last of set-up: collect, then freeze what set-up left (the
    imports, the compiled programs' and the weights' host objects), so
    that the window's collections scan only what serving allocates.  With
    set-up's heap unfrozen, a step waited 0.1-0.2 s longer than its
    device time every few seconds, the device idle (one TPU v5e)."""
    gc.collect()
    gc.freeze()


def configure_jax() -> None:
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def warm_up(engine, c: dict, vocab: int) -> None:
    """Compile every program the window drives (reset, prefill row,
    decode, results) with two short requests, then clear the engine."""
    import numpy as np
    n = c["engine"]["prefill_chunk"] + 1
    for i in range(2):
        engine.submit(np.arange(1, n + 1 + i, dtype=np.int32) % vocab, 3)
    engine.run()
    engine.reset()


def serve_window(engine, gen, mix: dict, rate, seconds: float, vocab: int,
                 seed: int, trace_dir: str | None, counter, gcw, root):
    """Set-up that the traffic needs, then the window, then serving on
    until every request due in it has its first token.  Returns the loop
    and the instant the window opened."""
    import jax
    from jax.profiler import TraceAnnotation
    import serve_loop as sl

    loop = sl.Loop(engine)
    tracer = {}

    def on_tick(t):
        if trace_dir is None:
            return
        if "ann" not in tracer and t >= tracer["at"]:
            jax.profiler.start_trace(trace_dir)
            tracer["ann"] = TraceAnnotation("traced_window")
            tracer["ann"].__enter__()
            tracer["t0"] = sl.now()
        elif "ann" in tracer and "t1" not in tracer and t >= tracer["at"] + TRACE_SECONDS:
            tracer["t1"] = sl.now()
            tracer["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()

    reqs = gen.make(mix, rate, seconds, vocab, seed, root)
    if gen.LOOP == "open":
        open_window()
        t_setup = sl.now()
        t_open = t_setup
        t_close = t_open + seconds
        tracer["at"] = t_open + max(0.0, seconds / 2 - TRACE_SECONDS / 2)
        loop.w.open_s, loop.w.close_s = t_open, t_close
        loop.record = True
        counter.on = gcw.on = True
        sl.serve_open(loop, reqs, t_open, t_close, on_tick)
    else:
        clients = sl.Clients(loop, reqs)
        first_rids = list(loop.w.events)
        # set-up the traffic needs: the first wave's prompts in the cache
        clients.serve(until=lambda: all(loop.w.events[r].token_s
                                        for r in first_rids))
        open_window()
        t_setup = sl.now()
        t_open, t_close = t_setup, t_setup + seconds
        tracer["at"] = t_open + max(0.0, seconds / 2 - TRACE_SECONDS / 2)
        loop.w.open_s, loop.w.close_s = t_open, t_close
        loop.record = True
        counter.on = gcw.on = True
        clients.serve(t_close=t_close, on_tick=on_tick)
    counter.on = gcw.on = False
    gc.unfreeze()             # so that the program's state can be freed
    loop.record = False
    if "ann" in tracer and "t1" not in tracer:
        on_tick(float("inf"))
    if "t1" in tracer:
        loop.w.trace_span = (tracer["t0"], tracer["t1"])
    # late first tokens are late, not missing: serve on (no new requests)
    due = [e.rid for e in loop.w.events.values()
           if t_open <= e.due_s < t_close]
    sl.drain_first_tokens(loop, due)
    return loop, t_setup


def run_cell(args, root: pathlib.Path = ROOT, require_chip: bool = True,
             fault=None, control: bool = False):
    """One run of a cell; returns the result line (a dict), the compared
    numbers, and with ``control`` the same numbers for the fp8 control on
    the same sample (``bench/control.py``; else None).

    For the benchmark's own tests: ``fault`` is called with the engine
    before anything compiles, to break the timed path underneath."""
    import registry
    bench = registry.load_benchmark(root)
    cell = registry.cell_entry(bench, args.workload)
    if require_chip:
        devs = check_device(cell["chips"])
    configure_jax()
    import jax
    from costs import Shapes
    from peaks import peaks_for
    from record import Record
    import correctness
    import model_setup

    if not require_chip:
        devs = jax.devices()
    dev = devs[0]
    c = registry.load_config(bench, cell["config"], root)
    mix = registry.load_traffic(cell["traffic"], root)
    gen = registry.module("generators", mix["generator"], root)
    if gen.max_tokens(mix) > c["engine"]["max_len"]:
        raise SystemExit(f"mix {cell['traffic']} sends up to {gen.max_tokens(mix)} "
                         f"tokens; the engine holds {c['engine']['max_len']}")
    rate = (registry.load_rate(args.workload, root)["rate_per_s"]
            if gen.LOOP == "open" else None)
    peaks = peaks_for(dev.device_kind) if require_chip else None
    limits = correctness.load_limits(args.workload, root)
    counter = CompileCounter()
    gcw = GcWatch()

    engine, params = model_setup.build_engine(c, args.seed, root)
    vocab = c["vocab_size"]
    log(f"autotune: {json.dumps(engine.paged_meta, sort_keys=True)}")
    if fault is not None:
        fault(engine)
    warm_up(engine, c, vocab)
    trace_dir = None
    if args.trace:
        trace_dir = str(root / "bench_out" / "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    loop, t_setup = serve_window(engine, gen, mix, rate, args.seconds, vocab,
                                 args.seed, trace_dir, counter, gcw, root)
    w = loop.w
    late = sorted(w.late_s)
    if late:
        log(f"generator late: p50 {late[len(late) // 2]!r} s, max {late[-1]!r} s "
            f"over {len(late)} arrivals")
    log(f"compilations inside the window: {counter.n}")
    log(f"oldest-generation collections inside the window: {len(gcw.pauses)}, "
        f"longest {max(gcw.pauses, default=0.0)!r} s")
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"peak_bytes_in_use: {peak}")

    served_until = max((t for e in w.events.values() for t in e.token_s),
                       default=w.close_s)
    results = engine.results()
    finished = {r: results[r] for r, e in w.events.items()
                if e.finish_s is not None and e.finish_s >= w.open_s and r in results}
    want_len = {r: w.events[r].max_new_tokens for r in finished}
    prompts = {r: loop.prompts[r] for r in finished}
    due = [e for e in w.events.values() if w.open_s <= e.due_s < w.close_s]
    attempted = len(due)
    failed = sum(1 for e in due if not e.token_s)
    if gen.LOOP == "closed":
        # closed loop: what the clients sent in the window, plus the
        # first wave in flight when it opened
        attempted = sum(1 for e in w.events.values()
                        if e.finish_s is None or e.finish_s >= w.open_s)
        failed = sum(1 for e in w.events.values()
                     if (e.finish_s is None or e.finish_s >= w.open_s)
                     and not e.token_s)
    rec = Record(cell=args.workload, config=c, shapes=Shapes.from_config(c),
                 peaks=peaks, window=w, setup_s=t_setup - T_START,
                 extra={"served_until_s": served_until})

    # free the program's state before the reference runs
    del loop, engine, results
    gc.collect()

    red = None
    if args.trace:
        import trace_reduce
        red = trace_reduce.reduce_trace(trace_reduce.find_xplane(trace_dir))
        rec.trace = red
        bounds = [rec.kernel_floor_s(s) for s in rec.traced_steps()]
        log(f"trace: busy {red['busy_s']!r} s of {red['window_s']!r} s; "
            f"kernel {red['kernel_s']!r} s in {red['kernel_events']} events; "
            f"its floor {sum(b[0] for b in bounds)!r} s (memory bound "
            f"{sum(b[1] for b in bounds)!r} s, compute bound "
            f"{sum(b[2] for b in bounds)!r} s) over {len(bounds)} steps; "
            f"modules {json.dumps(red['modules'])}")
    metrics = {}
    for m in registry.metrics_for(bench, args.workload, bool(args.trace)):
        v = registry.metric_reader(m["name"], root)(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks, control_checks = correctness.check(
        model_setup.reference(c, root), c, params, prompts, finished, want_len,
        limits, args.seed, mix["check_tokens"], control=control)
    ok = correctness.passed(checks)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = checks
    return result, checks, control_checks


def main(argv=None) -> int:
    args = parse(argv)
    result, checks, _ = run_cell(args)
    for name, v in checks.items():
        print(f"[check] {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
