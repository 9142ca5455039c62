"""The engine's own trace (serve/trace.py): the profiler spans around each
step and its phases, and the public per-step ``StepEvent``.

A tiny engine serves a workload that admits mid-run, preempts a request
and recycles its output rows, under ``jax.profiler.trace``; the tests
read the spans back from the trace file and hold the events against the
engine's private records and the tokens ``results()`` returns.
"""
import copy
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import reduced_config
from repro.models import build_model
from repro.serve import ContinuousBatchingEngine, OpenLoopFrontend
from repro.serve import trace
from repro.serve.arrivals import ArrivalRequest

pytestmark = pytest.mark.tier1


def _engine(model, params):
    # a 4-page budget cannot hold both long requests' growth: the younger
    # one is preempted mid-prefill and re-admitted; 6 output rows for 2
    # slots run out before results() is called, so an admission flushes
    return ContinuousBatchingEngine(model, params, n_slots=2, max_len=32,
                                    page_size=8, page_budget=4,
                                    prefill_chunk=4)


def _workload(vocab):
    rng = np.random.default_rng(3)
    reqs = [(np.arange(1, 16), 8), (np.arange(1, 21), 2)]
    reqs += [(rng.integers(1, vocab, 5), 3) for _ in range(6)]
    return reqs


def _spans(path):
    """(thread, name, start, end, step_num) of every program span."""
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("$") or not e.name.startswith("serve"):
                    continue
                num = dict(e.stats).get("step_num")
                out.append((k, e.name, e.start_ns, e.start_ns + e.duration_ns,
                            None if num is None else int(num)))
    return sorted(out, key=lambda sp: (sp[2], -sp[3]))


def _children(step, spans):
    return [sp for sp in spans if sp is not step and sp[0] == step[0]
            and step[2] <= sp[2] and sp[3] <= step[3]]


@pytest.fixture(scope="module")
def tiny_model():
    cfg = reduced_config("granite-3-2b")
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def traced(tiny_model, tmp_path_factory):
    cfg, model, params = tiny_model
    eng = _engine(model, params)
    reqs = _workload(cfg.vocab_size)
    for p, g in reqs:                  # compile every program first
        eng.submit(p, g)
    eng.run()
    eng.reset()
    rids = [eng.submit(p, g) for p, g in reqs]
    steps = []
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        while True:
            more = eng.step()
            ev = eng.last_event
            if ev is not None:
                steps.append(dict(
                    event=ev, ready_is_out_buf=ev.ready is eng._out_buf,
                    plan=eng.last_plan,
                    sampled=list(eng.last_sampled_rids),
                    admitted=list(eng.last_admitted_rids),
                    counts=dict(eng.sched.last_commit_counts)))
            if not more:
                break
        results = eng.results()
    path, = glob.glob(os.path.join(str(out), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return dict(steps=steps, spans=_spans(path), results=results, rids=rids,
                requests={r.rid: r for r in eng.requests()})


def test_every_planned_step_has_one_serve_step_with_its_step_num(traced):
    nums = [sp[4] for sp in traced["spans"] if sp[1] == trace.SERVE_STEP]
    want = [s["event"].step for s in traced["steps"]]
    assert want == list(range(len(want)))
    assert sorted(nums) == want


def test_phases_nest_inside_their_step_in_order(traced):
    spans = traced["spans"]
    steps = [sp for sp in spans if sp[1] == trace.SERVE_STEP]
    order = re.compile(r"P(A(F)?)?(D)?(R)*C")
    letter = {trace.PLAN: "P", trace.ADMIT: "A", trace.FLUSH: "F",
              trace.DECODE: "D", trace.PREFILL: "R", trace.COMMIT: "C"}
    for st, rec in zip(steps, traced["steps"]):
        ev = rec["event"]
        kids = _children(st, spans)
        seq = "".join(letter[sp[1]] for sp in kids)
        assert order.fullmatch(seq), (st[4], seq)
        assert seq.count("D") == (1 if ev.n_decode else 0)
        assert seq.count("R") == len(ev.prefills)
        # phases follow one another: each starts after the last ended
        top = [sp for sp in kids if sp[1] != trace.FLUSH]
        assert all(a[3] <= b[2] for a, b in zip(top, top[1:]))
    # a flush happens inside an admission (output rows ran out) and
    # after the run (results()), outside any step
    flushes = [sp for sp in spans if sp[1] == trace.FLUSH]
    inside = [f for f in flushes if any(f in _children(st, spans)
                                        for st in steps)]
    assert inside and len(inside) < len(flushes)


def test_admit_span_only_on_admitting_steps(traced):
    spans = traced["spans"]
    steps = [sp for sp in spans if sp[1] == trace.SERVE_STEP]
    admitting = 0
    for st, rec in zip(steps, traced["steps"]):
        has_admit = any(sp[1] == trace.ADMIT for sp in _children(st, spans))
        assert has_admit == bool(rec["plan"].reset_mask.any()), st[4]
        if rec["event"].admitted:
            assert has_admit
        admitting += has_admit
    assert 0 < admitting < len(steps)


def test_last_event_agrees_with_engine_records_and_results(traced):
    tokens = {}                        # rid -> tokens the stream committed
    finished = []
    preempted = []
    for rec in traced["steps"]:
        ev, plan = rec["event"], rec["plan"]
        assert rec["ready_is_out_buf"]
        assert ev.n_decode == plan.n_decode
        assert ev.decode_pos == tuple(
            int(plan.positions[s, 0]) for s in range(len(plan.n_valid))
            if plan.n_valid[s] > 0)
        assert ev.prefills == tuple(
            (int(p.positions[0, 0]), int(p.n_valid[0]), p.completes_prompt)
            for p in plan.prefills)
        assert ev.n_prefill_tokens == plan.n_prefill_tokens
        assert ev.sampled == tuple((rid, rec["counts"].get(slot, 1))
                                   for slot, rid in rec["sampled"])
        assert list(ev.admitted) == rec["admitted"]
        for rid in ev.preempted:
            tokens[rid] = 0            # a victim's tokens are thrown away
        preempted += ev.preempted
        for rid, c in ev.sampled:
            tokens[rid] = tokens.get(rid, 0) + c
        finished += ev.finished
    results, reqs = traced["results"], traced["requests"]
    assert preempted and sorted(set(preempted)) == sorted(
        rid for rid, r in reqs.items() if r.n_preemptions)
    assert sorted(finished) == sorted(traced["rids"]) == sorted(results)
    for rid in traced["rids"]:
        assert tokens[rid] == len(results[rid]) == reqs[rid].max_new_tokens


def test_span_names_are_the_trace_constants(traced):
    names = {sp[1] for sp in traced["spans"]}
    assert names == set(trace.SPANS)
    assert trace.SPANS == ("serve_step", "serve.plan", "serve.admit",
                           "serve.decode", "serve.prefill", "serve.commit",
                           "serve.flush")
    assert trace.PHASES == trace.SPANS[1:-1]


def _record_from_engine_state(eng, t, events, live):
    """The frontend's bookkeeping as it read the engine's private records
    before ``StepEvent``: the reference the event stream must match."""
    for rid, req in live.items():
        ev = events[rid]
        if req.n_preemptions > ev.n_preemptions:
            ev.n_preemptions = req.n_preemptions
            del ev.token_times_s[req.n_generated:]
    for rid in eng.last_admitted_rids:
        ev = events.get(rid)
        if ev is None:
            continue
        if ev.first_sched_s is None:
            ev.first_sched_s = t
        req = live.get(rid)
        if req is not None:
            ev.prefix_len = max(ev.prefix_len, req.prefix_len)
    counts = eng.sched.last_commit_counts
    for slot, rid in eng.last_sampled_rids:
        ev = events.get(rid)
        req = live.get(rid)
        if ev is None or req is None:
            continue
        c = int(counts.get(slot, 1))
        del ev.token_times_s[max(0, req.n_generated - c):]
        ev.token_times_s.extend([t] * c)
        ev.n_generated = req.n_generated
    for rid in [r for r, req in live.items() if req.finish_reason]:
        req = live.pop(rid)
        ev = events[rid]
        ev.finish_s = t
        ev.finish_reason = req.finish_reason
        ev.n_generated = req.n_generated


class _TwinFrontend(OpenLoopFrontend):
    """Records every step both from ``last_event`` and by the reference."""

    def __init__(self, engine):
        super().__init__(engine, clock="model")
        self.ref, self.ref_live = {}, {}

    def _record_step(self, t, events, live):
        for rid, req in live.items():
            if rid not in self.ref:
                self.ref[rid] = copy.deepcopy(events[rid])
                self.ref_live[rid] = req
        _record_from_engine_state(self.engine, t, self.ref, self.ref_live)
        super()._record_step(t, events, live)


def test_frontend_events_match_the_private_state_reference(tiny_model):
    cfg, model, params = tiny_model
    eng = _engine(model, params)
    # the two long requests arrive together, as in the traced run; the
    # short ones trickle in on the model clock
    arr = [ArrivalRequest(arrival_s=max(0, i - 1) * 1e-6, prompt=p,
                          max_new_tokens=g)
           for i, (p, g) in enumerate(_workload(cfg.vocab_size))]
    front = _TwinFrontend(eng)
    res = front.run(arr)
    assert sum(e.n_preemptions for e in res.events) >= 1
    assert all(e.completed for e in res.events)
    assert res.events == [front.ref[r] for r in sorted(front.ref)]
