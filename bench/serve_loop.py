"""The serving loop the window drives: the program's public engine API
(``submit`` and ``step``) on a real clock.

Open loop: each request is submitted once its due time has passed, and
the loop sleeps until the next one when the engine has no work.  Closed
loop: each client sends its next request when its previous one finishes.
After every ``step()`` the loop waits for the step's outputs, as a
streaming server must before it sends a token, and only then stamps the
step's tokens.  TTFT runs from the due time, not from the submission.

The event bookkeeping (preemption truncation, multi-token commits)
follows ``repro.serve.frontend.OpenLoopFrontend._record_step``.

Beyond ``submit``, ``step`` and ``results``, the program has no public
stream of a step's tokens, so the loop reads these engine attributes,
and only here: ``last_plan`` (its ``n_decode``, ``n_valid``,
``positions`` and ``prefills``), ``last_sampled_rids``,
``last_admitted_rids``, ``sched.queue``, ``sched.has_work()``,
``sched.last_commit_counts``, each request's ``n_generated``,
``n_preemptions`` and ``finish_reason``, and ``_out_buf`` (see
``step_outputs``).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
from jax.profiler import TraceAnnotation


IDLE_SLICE_S = 0.05


def now() -> float:
    return time.perf_counter()


def step_outputs(engine):
    """The device array that holds every request's sampled tokens, which
    each step's last program writes: once it is ready, so are the step's
    tokens, whatever the layout of the cache the step also wrote."""
    return engine._out_buf


@dataclass
class Events:
    rid: int
    due_s: float              # absolute host time
    prompt_len: int
    max_new_tokens: int
    client: int = -1
    admitted_s: float | None = None
    token_s: list = field(default_factory=list)
    finish_s: float | None = None
    n_preemptions: int = 0


@dataclass
class Step:
    start_s: float
    done_s: float
    n_decode: int
    decode_pos: list          # position of each decode row's query
    prefills: list            # (start position, valid tokens, samples)


@dataclass
class Window:
    open_s: float = 0.0
    close_s: float = 0.0
    events: dict = field(default_factory=dict)    # rid -> Events
    steps: list = field(default_factory=list)
    late_s: list = field(default_factory=list)    # generator lateness
    trace_span: tuple | None = None               # host (start, stop)


class Loop:
    """Drives one engine; ``run_until`` serves until a host time."""

    def __init__(self, engine):
        self.engine = engine
        self.w = Window()
        self.live = {}            # rid -> scheduler Request
        self.prompts = {}         # rid -> prompt tokens
        self.record = False       # keep steps (inside the window only)

    def submit(self, r, due_s: float) -> int:
        eng = self.engine
        rid = eng.submit(r.prompt, r.max_new_tokens, temperature=0.0)
        req = eng.sched.queue[-1]
        assert req.rid == rid
        self.live[rid] = req
        self.prompts[rid] = r.prompt
        self.w.events[rid] = Events(rid, due_s, len(r.prompt),
                                    r.max_new_tokens, r.client)
        return rid

    def step(self) -> bool:
        """One engine step and the wait for its outputs; False when the
        engine had nothing to run."""
        eng = self.engine
        t0 = now()
        with TraceAnnotation("engine.step"):
            eng.step()
        plan = eng.last_plan
        if plan is None:
            return False
        with TraceAnnotation("wait_outputs"):
            jax.block_until_ready(step_outputs(eng))
        t = now()
        self._record(plan, t)
        if self.record:
            dec = [int(plan.positions[s, 0]) for s in range(len(plan.n_valid))
                   if plan.n_valid[s] > 0]
            pre = [(int(p.positions[0, 0]), int(p.n_valid[0]),
                    bool(p.completes_prompt)) for p in plan.prefills]
            self.w.steps.append(Step(t0, t, plan.n_decode, dec, pre))
        return True

    def _record(self, plan, t: float) -> None:
        eng = self.engine
        for rid, req in self.live.items():
            ev = self.w.events[rid]
            if req.n_preemptions > ev.n_preemptions:
                ev.n_preemptions = req.n_preemptions
                del ev.token_s[req.n_generated:]
        for rid in eng.last_admitted_rids:
            ev = self.w.events.get(rid)
            if ev is not None and ev.admitted_s is None:
                ev.admitted_s = t
        counts = eng.sched.last_commit_counts
        for slot, rid in eng.last_sampled_rids:
            ev, req = self.w.events.get(rid), self.live.get(rid)
            if ev is None or req is None:
                continue
            c = int(counts.get(slot, 1))
            del ev.token_s[max(0, req.n_generated - c):]
            ev.token_s.extend([t] * c)
        for rid in [r for r, q in self.live.items() if q.finish_reason]:
            self.live.pop(rid)
            self.w.events[rid].finish_s = t

    def idle_until(self, t: float) -> None:
        """Sleep towards ``t``, a slice at a time, so that the caller's
        tick (which starts and stops the trace) runs while idle."""
        with TraceAnnotation("idle_until_arrival"):
            dt = min(t - now(), IDLE_SLICE_S)
            if dt > 0:
                time.sleep(dt)


def serve_open(loop: Loop, reqs: list, t_open: float, t_close: float,
               on_tick=None) -> None:
    """Open loop over ``reqs`` (due seconds after ``t_open``) until
    ``t_close``."""
    i = 0
    while True:
        t = now()
        if on_tick is not None:
            on_tick(t)
        if t >= t_close:
            return
        with TraceAnnotation("submit"):
            while i < len(reqs) and t_open + reqs[i].due_s <= t:
                due = t_open + reqs[i].due_s
                loop.w.late_s.append(t - due)
                loop.submit(reqs[i], due)
                i += 1
        if loop.engine.sched.has_work():
            loop.step()
        elif i < len(reqs):
            loop.idle_until(min(t_open + reqs[i].due_s, t_close))
        else:
            loop.idle_until(t_close)


class Clients:
    """Closed loop: one client per queue; each sends its next request
    as soon as its last one finishes."""

    def __init__(self, loop: Loop, queues: list):
        self.loop, self.queues = loop, queues
        self.nxt = [0] * len(queues)
        self.busy = {}            # client -> rid in flight
        with TraceAnnotation("submit"):
            for c in range(len(queues)):
                self._send(c)

    def _send(self, c: int) -> None:
        if self.nxt[c] < len(self.queues[c]):
            self.busy[c] = self.loop.submit(self.queues[c][self.nxt[c]], now())
            self.nxt[c] += 1
        else:
            self.busy.pop(c, None)

    def serve(self, t_close: float | None = None, until=None,
              on_tick=None) -> None:
        """Serve until host time ``t_close``, or until ``until()`` holds."""
        loop = self.loop
        while True:
            t = now()
            if on_tick is not None:
                on_tick(t)
            if (t_close is not None and t >= t_close) or (
                    until is not None and until()):
                return
            with TraceAnnotation("submit"):
                for c, rid in list(self.busy.items()):
                    if loop.w.events[rid].finish_s is not None:
                        self._send(c)
            if not loop.step() and not self.busy:
                raise RuntimeError("closed loop: every client ran out of "
                                   "requests")


def drain_first_tokens(loop: Loop, rids, limit_s: float = 60.0) -> None:
    """After the window: serve on, without new requests, until each of
    ``rids`` has its first token (at most ``limit_s``)."""
    t_end = now() + limit_s
    pending = [r for r in rids if not loop.w.events[r].token_s]
    while pending and now() < t_end and loop.engine.sched.has_work():
        loop.step()
        pending = [r for r in pending if not loop.w.events[r].token_s]
