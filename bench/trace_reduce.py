"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time, time per jitted module, the paged
kernel's time, the longest idle gaps by what the host was doing, and the
device operations that took most time.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run, named by its HLO text (``%fusion.12 = ...``),
and their ``XLA Modules`` line one per program run, named after the
jitted function (``jit_decode_step(<id>)``).  The paged kernel's custom
call carries the kernel's name (``%paged_attention.4 = ... custom-call``).  The
host spans are the benchmark's own ``TraceAnnotation``s.  Everything is
read on the trace's one clock, inside the host span ``traced_window``.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

HOST_SPANS = ("submit", "engine.step", "wait_outputs", "idle_until_arrival")
WINDOW_SPAN = "traced_window"
KERNEL_NAME = re.compile(r"paged_attention(\.\d+)?$")
# control flow spans the operations it runs: kept for the busy union,
# left out of the list of operations that took most time
CONTROL_FLOW = re.compile(r"(while|conditional|call)(\.\d+)?$")


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns), e


def op_name(event_name: str) -> str:
    """``fusion.12`` from ``%fusion.12 = bf16[...] fusion(...)``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    out = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(t, hi)) for s, t in intervals if t > lo and s < hi]


def is_kernel(name: str) -> bool:
    return bool(KERNEL_NAME.match(op_name(name)))


def reduce_trace(path: str) -> dict:
    """The trace's numbers, in seconds, averaged over the chips traced."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host, devices = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in plane.name:
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            host.append(plane)
    spans = []                 # (name, start, end) of the benchmark's spans
    for plane in host:
        for line in plane.lines:
            for name, s, d, _ in _events(line):
                if name in HOST_SPANS or name == WINDOW_SPAN:
                    spans.append((name, s, s + d))
    win = [(s, t) for n, s, t in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = win[0]
    if not devices:
        raise ValueError("no device plane in the trace")
    busy_ns, kernel_ns, n_kernel = 0.0, 0.0, 0
    modules = defaultdict(lambda: [0, 0.0])
    ops = defaultdict(float)
    gaps = []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        op_iv = []
        op_lines = [ln for n, ln in lines.items() if n.startswith("XLA Ops")]
        for name, s, d, _ in (ev for ln in op_lines for ev in _events(ln)):
            if s + d <= lo or s >= hi:
                continue
            op_iv.append((s, s + d))
            short = op_name(name)
            if not CONTROL_FLOW.match(short):
                ops[short] += d
            if is_kernel(name):
                kernel_ns += d
                n_kernel += 1
        merged = _clip(_union(op_iv), lo, hi)
        busy_ns += sum(t - s for s, t in merged)
        for name, s, d, _ in _events(lines.get("XLA Modules", ())):
            if s + d <= lo or s >= hi:
                continue
            key = re.sub(r"\(\d+\)$", "", name)
            modules[key][0] += 1
            modules[key][1] += d
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_label(spans, (s + t) / 2), (t - s) / 1e9] for s, t in gaps[:10]]
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {
        "n_devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "kernel_s": kernel_ns / n / 1e9,
        "kernel_events": n_kernel // n,
        "modules": {k: {"calls": c // n, "seconds": t / n / 1e9}
                    for k, (c, t) in modules.items()},
        "device_ops": [[k, v / n / 1e9] for k, v in top],
        "idle_gaps": idle,
    }


def _label(spans, t: float) -> str:
    """The innermost benchmark span on the host at time ``t``."""
    best = None
    for name, s, e in spans:
        if name != WINDOW_SPAN and s <= t <= e and (
                best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside_spans"


def module_time(red: dict, fn_name: str):
    """(calls, seconds) of the jitted function ``fn_name``'s programs."""
    calls, secs = 0, 0.0
    for k, v in red["modules"].items():
        if re.fullmatch(rf"jit_{re.escape(fn_name)}(\.\d+)?", k):
            calls += v["calls"]
            secs += v["seconds"]
    return calls, secs
