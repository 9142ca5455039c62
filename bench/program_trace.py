"""The engine's own spans in the traced run's profiler trace, beside its
device planes: how long the host spends in each phase of a step, how
much device idle falls while the host is inside a step, and which step
dispatched each program run.

The spans are the names ``repro.serve.trace`` defines: ``serve_step``
(carrying the engine's ``step_num``) around each step, and its phases
``serve.plan``, ``serve.admit``, ``serve.decode``, ``serve.prefill`` and
``serve.commit`` inside it.  They are taken from the host plane (the
Python tracer's events, named ``$...``, are skipped), nested by interval
on their thread, and only the steps that lie wholly inside the
benchmark's ``traced_window`` count.  Device idle is taken the way
``trace_reduce`` takes busy time, with its own helpers.

The profiler's device clock is not the host's: on one TPU v5e, device
programs appear to start 0.4-1.5 ms before the host began to enqueue
them, by an amount that differs from trace to trace.  So before device
intervals meet host spans, each device's times are shifted by the least
amount under which no program run starts before its enqueue (the TPU
runtime's ``DoEnqueueProgram`` host event, joined to the run by
``run_id``).

``run.py --trace 1`` writes the trace under ``<root>/bench_out/trace``;
it is parsed once per process.  A program without these spans gives
None for every value, and so does an untraced run.
"""
from __future__ import annotations

import functools
import pathlib
import re

from trace_reduce import WINDOW_SPAN, _clip, _union, find_xplane

ROOT = pathlib.Path(__file__).resolve().parent.parent
TRACE_DIR = ROOT / "bench_out" / "trace"

# the jitted programs joined to the phase span that dispatches each
PROGRAMS = ("jit_decode_step", "jit_prefill_row")
# the TPU runtime's host event that enqueues a program run (its run_id is
# the device module's)
ENQUEUE = "DoEnqueueProgram"


def _span_names():
    """The program's span names, or None where the program has none."""
    try:
        from repro.serve import trace
    except ImportError:
        return None
    return trace


def _overlap(a, b) -> float:
    """Total length of the intersection of two sorted disjoint interval
    lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _covered(children) -> float:
    return sum(t - s for s, t in _union(children))


def _host_events(data, tr):
    """Program spans ``(thread, name, start, end, step_num)``, the
    benchmark's window, and the host instant each program run was
    enqueued (by ``run_id``), from the host planes."""
    spans, window, enqueued = [], None, {}
    names = set(tr.SPANS)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                name = e.name
                if name.startswith("$"):
                    continue
                s, t = float(e.start_ns), float(e.start_ns + e.duration_ns)
                if name in names:
                    num = None
                    if name == tr.SERVE_STEP:
                        num = dict(e.stats).get("step_num")
                    spans.append(((plane.name, k), name, s, t, num))
                elif name == WINDOW_SPAN and window is None:
                    window = (s, t)
                elif name == ENQUEUE:
                    run = dict(e.stats).get("run_id")
                    if run is not None:
                        enqueued[run] = s
    return spans, window, enqueued


def _device_events(plane, lo, hi, enqueued):
    """One device's idle intervals in the window (on its own clock, as
    ``trace_reduce`` takes busy time), its program runs' starts by
    module, and the shift that puts its clock on the host's: the least
    under which no program starts before the host began to enqueue it.
    The shift is None where no run can be joined to its enqueue."""
    op_iv, runs, shift = [], {k: [] for k in PROGRAMS}, None
    for line in plane.lines:
        if line.name.startswith("XLA Ops"):
            op_iv += [(float(e.start_ns), float(e.start_ns + e.duration_ns))
                      for e in line.events]
        elif line.name == "XLA Modules":
            for e in line.events:
                host = enqueued.get(dict(e.stats).get("run_id"))
                if host is not None:
                    d = host - float(e.start_ns)
                    shift = d if shift is None else max(shift, d)
                key = re.sub(r"\(\d+\)$", "", e.name)
                if key in runs:
                    runs[key].append(float(e.start_ns))
    busy = _clip(_union([(s, t) for s, t in op_iv if t > lo and s < hi]), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return idle, runs, shift


@functools.lru_cache(maxsize=None)
def reduce_spans(path: str):
    """The step phases' host time, the idle inside steps and the join of
    jitted programs to their dispatch, in seconds; None where the trace
    holds no ``serve_step`` of the program."""
    tr = _span_names()
    if tr is None:
        return None
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans, window, enqueued = _host_events(data, tr)
    devices = [p for p in data.planes if p.name.startswith("/device:TPU:")
               and "SparseCore" not in p.name]
    if window is None or not devices:
        return None
    lo, hi = window
    steps = [sp for sp in spans if sp[1] == tr.SERVE_STEP and sp[4] is not None
             and sp[2] >= lo and sp[3] <= hi]
    if not steps:
        return None
    phases = [sp for sp in spans if sp[1] != tr.SERVE_STEP]

    def inside(outer, sp):
        return (sp is not outer and sp[0] == outer[0] and sp[2] >= outer[2]
                and sp[3] <= outer[3])

    def self_s(sp):
        kids = [(c[2], c[3]) for c in phases if inside(sp, c)]
        return (sp[3] - sp[2] - _covered(kids)) / 1e9

    sched = launch = step_s = 0.0
    phase_of = dict(zip(PROGRAMS, (tr.DECODE, tr.PREFILL)))
    dispatch = {v: [] for v in phase_of.values()}   # phase -> [(start, step)]
    for st in steps:
        step_s += (st[3] - st[2]) / 1e9
        for sp in (p for p in phases if inside(st, p)):
            if sp[1] in (tr.PLAN, tr.COMMIT):
                sched += self_s(sp)
            elif sp[1] in (tr.ADMIT, tr.DECODE, tr.PREFILL):
                launch += (sp[3] - sp[2]) / 1e9
            if sp[1] in dispatch:
                dispatch[sp[1]].append((sp[2], int(st[4])))
    in_step = _union([(sp[2], sp[3]) for sp in spans if sp[1] == tr.SERVE_STEP])
    idle_in_step, shifts = 0.0, []
    joins = {k: {"runs": 0, "dispatches": 0, "matched": 0, "steps": []}
             for k in PROGRAMS}
    for plane in devices:
        idle, runs, shift = _device_events(plane, lo, hi, enqueued)
        shifts.append(shift)
        if shift is None:
            continue
        idle_in_step += _overlap([(s + shift, t + shift) for s, t in idle],
                                 in_step)
        for module, phase in phase_of.items():
            got = _join([r + shift for r in runs[module] if lo <= r + shift < hi],
                        sorted(dispatch[phase]))
            for key in ("runs", "dispatches", "matched"):
                joins[module][key] += got[key]
            joins[module]["steps"] += got["steps"]
    aligned = all(d is not None for d in shifts)
    return {
        "window_s": (hi - lo) / 1e9,
        "n_steps": len(steps),
        "step_s": step_s,
        "sched_host_s": sched,
        "launch_host_s": launch,
        "clock_shift_s": [None if d is None else d / 1e9 for d in shifts],
        "idle_in_step_s": (idle_in_step / len(devices) / 1e9 if aligned
                           else None),
        "dispatch": joins,
    }


def _join(runs, dispatches) -> dict:
    """Program runs (on the host clock) paired in order with the
    dispatches of their program: a device runs one program at a time in
    the order it was sent, so each dispatch takes the first unpaired run
    that starts after it.  A run that starts before the next unpaired
    dispatch has none of its own and stays unmatched."""
    j, steps = 0, []
    for r in sorted(runs):
        if j < len(dispatches) and dispatches[j][0] <= r:
            steps.append(dispatches[j][1])
            j += 1
    return {"runs": len(runs), "dispatches": len(dispatches),
            "matched": len(steps), "steps": steps}


def metrics(path: str):
    """The three per-layer values from the trace at ``path``, or None."""
    red = reduce_spans(path)
    if red is None:
        return None
    n = red["n_steps"]
    return {
        "sched_host_ms": 1e3 * red["sched_host_s"] / n,
        "launch_host_ms": 1e3 * red["launch_host_s"] / n,
        "idle_in_step_share": (
            None if red["idle_in_step_s"] is None
            else 100.0 * red["idle_in_step_s"] / red["window_s"]),
    }


def value(rec, name: str):
    """One of ``metrics``' values for the traced run ``rec``, or None."""
    if rec.trace is None:
        return None
    try:
        path = find_xplane(str(TRACE_DIR))
    except FileNotFoundError:
        return None
    got = metrics(path)
    return None if got is None else got[name]
