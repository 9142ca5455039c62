"""Open-loop serving front end: a virtual-clock intake loop over
``ContinuousBatchingEngine``.

Closed-loop serving (``engine.run()``) answers "how fast can the engine
drain a queue"; it cannot answer "how long does a user wait when
requests *arrive* faster or slower than the engine drains them" — TTFT,
time-between-tokens, and goodput under load are properties of a system
with a clock.  :class:`OpenLoopFrontend` supplies that clock:

  * it takes a list of :class:`~repro.serve.arrivals.ArrivalRequest`
    records (any generator in ``serve/arrivals.py``),
  * submits each one the moment the virtual clock passes its
    ``arrival_s`` (enqueue-time prefix matching comes for free: the
    scheduler hashes the prompt's prefix keys at ``submit()``, so a
    queued request admits at its matched offset the instant a slot
    frees),
  * calls ``engine.step()`` between arrivals, and
  * records per-request event timestamps — arrival, enqueue, first
    scheduled, every kept token, finish — as
    :class:`~repro.serve.slo.RequestEvents` for ``slo.latency_summary``.

Two clocks, one loop:

``clock="wall"``
    The virtual clock advances by each step's measured wall, bracketed
    exclusively with ``perf.measure.now()`` (the timing-confinement
    invariant: no other timing call exists in this module).  This is
    the *measurement* clock — serve_bench's open-loop scenario runs it.

``clock="model"``
    The clock advances by ``engine.modeled_step_time()`` — the
    costmodel's roofline bound time for each step's actual composition.
    Fully deterministic (no wall ever read), so tests can assert exact
    event orderings, rate accuracy, and chunk-policy TBT bounds without
    host-noise flakes.  The frontend also feeds the modeled times into
    the scheduler's stall-free chunk estimator (``note_step_wall``),
    replacing the engine's wall feedback (``step_feedback`` is set to
    ``"external"`` for the duration of the run and restored after).

Idle jumps: when the engine has no work and arrivals remain, the clock
jumps straight to the next arrival — open-loop runs never spin.  A
planless iteration *with* work queued means the scheduler cannot place
anything (page budget below a single request's first chunk); after the
same patience window as ``engine.run()`` that raises instead of
hanging.

Closed-loop compatibility: under ``arrivals.closed_loop_arrivals`` every
request is submitted before the first step, so the step sequence — and
at temperature 0 the token output — is exactly ``engine.submit()``\\*N +
``engine.run()`` (pinned by tests/test_serve_frontend.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.perf.measure import now
from repro.serve.arrivals import ArrivalRequest
from repro.serve.slo import SLO, RequestEvents, latency_summary

CLOCKS = ("wall", "model")


@dataclasses.dataclass
class OpenLoopResult:
    """One open-loop run: per-request event records, the generated
    tokens, and the raw queue-depth samples (``(t, depth)``)."""
    events: List[RequestEvents]
    results: Dict[int, np.ndarray]
    makespan_s: float
    queue_depth: List[Tuple[float, int]]
    engine_summary: Dict[str, Any]
    clock: str
    # the ArrivalRequest records of every request that *finished* during
    # the run, in rid order — ``arrivals.save_trace`` serializes them
    # under the repro.serve.trace schema, so any open-loop run can be
    # re-played deterministically (launch/serve.py --record-trace)
    completed_arrivals: List[ArrivalRequest] = dataclasses.field(
        default_factory=list)

    def summary(self, slo: Optional[SLO] = None) -> Dict[str, Any]:
        """The schema-valid ``latency`` block (slo.latency_summary)."""
        return latency_summary(self.events, slo=slo,
                               makespan_s=self.makespan_s,
                               queue_depth=self.queue_depth)


class OpenLoopFrontend:
    """Virtual-clock intake loop over a ``ContinuousBatchingEngine``.

    Usage::

        eng = ContinuousBatchingEngine(model, params, n_slots=4,
                                       max_len=128)
        reqs = arrivals.synthetic_requests(32, (8, 16), (4, 8), V)
        front = OpenLoopFrontend(eng)
        res = front.run(arrivals.poisson_arrivals(reqs, rate=2.0))
        res.summary(slo=SLO(ttft_s=0.5, tbt_s=0.1))

    The frontend owns no engine state: it submits, steps, and reads the
    engine's public per-step record (``last_event``, a
    ``serve.trace.StepEvent``); ``engine.reset()`` between runs reuses
    the compiled step functions.
    """

    def __init__(self, engine, *, clock: str = "wall"):
        if clock not in CLOCKS:
            raise ValueError(f"clock {clock!r} not in {CLOCKS}")
        self.engine = engine
        self.clock = clock

    # -- event recording -------------------------------------------------
    def _record_step(self, t: float, events: Dict[int, RequestEvents],
                     live: Dict[int, Any]) -> None:
        """Fold the engine's last ``StepEvent`` into the event map.
        Ordering matters: preemption truncation first (discarded tokens
        leave ``token_times_s``), then first-schedule marks, then this
        step's kept tokens, then finishes."""
        step = self.engine.last_event
        # recompute-style preemption throws away a victim's sampled
        # tokens; the event record must not keep their timestamps (TBT /
        # TTFT describe what a client would actually have streamed)
        for rid in step.preempted:
            req = live.get(rid)
            if req is None:
                continue
            ev = events[rid]
            ev.n_preemptions = req.n_preemptions
            del ev.token_times_s[req.n_generated:]
        for rid in step.admitted:
            ev = events.get(rid)
            if ev is None:        # pre-queued outside this frontend run
                continue
            if ev.first_sched_s is None:
                ev.first_sched_s = t
            req = live.get(rid)
            if req is not None:
                ev.prefix_len = max(ev.prefix_len, req.prefix_len)
        for rid, c in step.sampled:
            ev = events.get(rid)
            req = live.get(rid)
            if ev is None or req is None:
                continue
            # a speculative step commits c >= 1 tokens at once; all c
            # share this step's completion instant, producing c - 1 zero
            # TBT gaps (the multi-token event contract — see serve/slo).
            # Without speculation c == 1 and this is the classic append.
            # Belt-and-braces against stale pre-preemption timestamps:
            # this step committed tokens n_generated-c+1 .. n_generated
            # (commit already ran), so exactly n_generated-c earlier
            # times stay
            del ev.token_times_s[max(0, req.n_generated - c):]
            ev.token_times_s.extend([t] * c)
            ev.n_generated = req.n_generated
        for rid in step.finished:
            req = live.pop(rid, None)
            if req is None:
                continue
            ev = events[rid]
            ev.finish_s = t
            ev.finish_reason = req.finish_reason
            ev.n_generated = req.n_generated

    # -- the loop --------------------------------------------------------
    def run(self, arrivals: Sequence[ArrivalRequest], *,
            max_steps: Optional[int] = None,
            start_s: float = 0.0) -> OpenLoopResult:
        """Drive the workload to completion; returns the event records
        and every request's generated tokens."""
        eng = self.engine
        arr = sorted(arrivals, key=lambda a: a.arrival_s)
        events: Dict[int, RequestEvents] = {}
        arecs: Dict[int, ArrivalRequest] = {}  # rid -> submitted arrival
        live: Dict[int, Any] = {}          # rid -> scheduler Request
        depth: List[Tuple[float, int]] = []
        t = start_s
        i = 0
        n_steps = 0
        stalled = 0
        prev_feedback = eng.step_feedback
        if self.clock == "model":
            # the frontend feeds deterministic modeled step times into
            # the stall-free chunk estimator; wall feedback would leak
            # host noise into an otherwise reproducible run
            eng.step_feedback = "external"
        try:
            while i < len(arr) or eng.sched.has_work():
                while i < len(arr) and arr[i].arrival_s <= t:
                    a = arr[i]
                    rid = eng.submit(a.prompt, a.max_new_tokens,
                                     temperature=a.temperature,
                                     extra=a.extra)
                    req = eng.sched.queue[-1]
                    assert req.rid == rid
                    arecs[rid] = a
                    live[rid] = req
                    events[rid] = RequestEvents(
                        rid=rid, arrival_s=a.arrival_s, enqueue_s=t,
                        prompt_len=req.prompt_len,
                        max_new_tokens=req.max_new_tokens)
                    i += 1
                depth.append((t, len(eng.sched.queue)))
                if not eng.sched.has_work():
                    # idle engine: the clock jumps to the next arrival
                    t = max(t, arr[i].arrival_s)
                    continue
                if self.clock == "wall":
                    t0 = now()
                    eng.step()
                    dt = now() - t0
                else:
                    eng.step()
                    step = eng.last_event
                    dt = (eng.modeled_step_time(step.n_decode,
                                                step.n_prefill_tokens)
                          if step is not None else 0.0)
                    if step is not None:
                        eng.sched.note_step_wall(
                            dt, step.n_decode + step.n_prefill_tokens)
                if eng.last_event is None:
                    # work queued but nothing placeable; submitting more
                    # requests cannot free pages, so this is the same
                    # dead state engine.run() guards against
                    stalled += 1
                    if stalled > eng.n_slots + 2:
                        raise RuntimeError(
                            "open-loop frontend stalled: work queued but "
                            "no step can run (page budget too small for "
                            "an in-flight request?)")
                    continue
                stalled = 0
                t += dt
                n_steps += 1
                self._record_step(t, events, live)
                if max_steps is not None and n_steps >= max_steps:
                    break
        finally:
            eng.step_feedback = prev_feedback
        depth.append((t, len(eng.sched.queue)))
        return OpenLoopResult(
            events=[events[r] for r in sorted(events)],
            results=eng.results(),
            makespan_s=t - start_s,
            queue_depth=depth,
            engine_summary=eng.stats.summary(),
            clock=self.clock,
            completed_arrivals=[
                arecs[r] for r in sorted(arecs)
                if events[r].finish_reason is not None])
