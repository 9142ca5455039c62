"""Serving subsystem: paged-cache accounting, scheduler composition, and
continuous-batching decode equivalence against the fixed-batch baseline."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import reduced_config
from repro.models import build_model
from repro.serve import (
    ContinuousBatchingEngine,
    EngineStats,
    PagedKVCache,
    PageTable,
    RequestState,
    Scheduler,
    StaticBatchEngine,
)

pytestmark = pytest.mark.tier1


# ---------------------------------------------------------------------------
# page table / paged cache (host-only, no jax)
# ---------------------------------------------------------------------------
def test_page_table_alloc_free_cycle():
    pt = PageTable(n_pages=4, page_size=8)
    assert pt.n_free == 4
    a = pt.alloc(3)
    assert pt.n_free == 1 and pt.n_used == 3
    assert not pt.can_alloc(2)
    with pytest.raises(RuntimeError):
        pt.alloc(2)
    pt.free(a)
    assert pt.n_free == 4 and pt.n_used == 0
    assert pt.pages_for(1) == 1 and pt.pages_for(8) == 1
    assert pt.pages_for(9) == 2


def test_paged_cache_slot_recycling():
    kv = PagedKVCache(n_slots=2, max_len=32, page_size=8)
    s0 = kv.admit(first_chunk=8)
    s1 = kv.admit(first_chunk=8)
    assert {s0, s1} == {0, 1} and not kv.free_slots
    assert not kv.can_admit(8)
    assert kv.grow(s0, 8) and kv.length(s0) == 8
    # growth allocates pages lazily across boundaries
    assert kv.grow(s0, 9) and kv.length(s0) == 17
    assert kv.slots[s0].pages and len(kv.slots[s0].pages) == 3
    # capacity is a hard bound
    assert not kv.grow(s0, 32)
    kv.release(s0)
    assert s0 in kv.free_slots and kv.can_admit(8)
    # recycled slot starts fresh
    s2 = kv.admit(first_chunk=8)
    assert s2 == s0 and kv.length(s2) == 0


def test_paged_cache_page_budget_blocks_admission():
    kv = PagedKVCache(n_slots=4, max_len=32, page_size=8, page_budget=3)
    kv.admit(first_chunk=16)                   # 2 pages
    assert kv.grow(0, 16)
    assert not kv.can_admit(16)                # 1 page left, needs 2
    assert kv.can_admit(8)


def test_page_double_free_raises_named_error():
    # regression: freeing a non-allocated page used to raise a bare
    # KeyError from set.remove — with refcounted prefix sharing a silent
    # or cryptic double release is a real hazard
    pt = PageTable(n_pages=4, page_size=8)
    pages = pt.alloc(2)
    pt.free(pages)
    with pytest.raises(RuntimeError, match=f"page {pages[0]}"):
        pt.free([pages[0]])
    with pytest.raises(RuntimeError, match="not allocated"):
        pt.incref([pages[0]])


def test_slot_double_release_raises_named_error():
    # regression: releasing a free slot used to raise a bare KeyError
    # from dict.pop
    kv = PagedKVCache(n_slots=2, max_len=32, page_size=8)
    s = kv.admit(first_chunk=8)
    kv.release(s)
    with pytest.raises(RuntimeError, match=f"slot {s}"):
        kv.release(s)
    with pytest.raises(RuntimeError, match="slot 1"):
        kv.release(1)                          # never admitted at all
    assert kv.table.n_used == 0


def test_admission_allocates_atomically():
    # regression: admit() used to make two separate alloc calls (prompt
    # chunk, then aux) after one can_admit check — a budget that covers
    # the chunk but not the aux tail must fail cleanly without leaking
    # the chunk pages
    kv = PagedKVCache(n_slots=2, max_len=32, page_size=8,
                      slot_aux_tokens=20, page_budget=3)  # needs 1 + 3 aux
    assert not kv.can_admit(8)
    with pytest.raises(RuntimeError):
        kv.admit(first_chunk=8)
    assert kv.table.n_used == 0                # nothing leaked
    assert kv.free_slots == [0, 1]


# ---------------------------------------------------------------------------
# scheduler (host-only)
# ---------------------------------------------------------------------------
def test_scheduler_admission_and_chunked_prefill():
    kv = PagedKVCache(n_slots=2, max_len=32, page_size=8)
    sched = Scheduler(kv, prefill_chunk=4)
    a = sched.submit(np.arange(1, 11), max_new_tokens=3)     # 10 tokens
    b = sched.submit(np.arange(1, 5), max_new_tokens=3)      # 4 tokens
    c = sched.submit(np.arange(1, 4), max_new_tokens=3)      # queued: no slot
    plan = sched.next_plan(step=0)
    # both free slots admitted; each gets a prompt chunk this step
    assert a.state is RequestState.PREFILLING
    assert b.state is RequestState.PREFILLING
    assert c.state is RequestState.QUEUED
    assert plan.prefill_chunks == {a.slot: 4, b.slot: 4}
    assert plan.reset_mask.sum() == 2
    # b's chunk covers its whole prompt -> it samples token #1
    assert b.slot in plan.sample_slots and a.slot not in plan.sample_slots
    sched.commit(plan, None, step=0)
    assert b.state is RequestState.DECODING
    assert a.prompt_pos == 4

    # drive a to completion of its prompt
    plan = sched.next_plan(step=1)
    assert plan.prefill_chunks == {a.slot: 4}
    assert plan.n_decode == 1                   # b decodes alongside
    sched.commit(plan, None, step=1)
    plan = sched.next_plan(step=2)
    assert plan.prefill_chunks == {a.slot: 2}   # ragged final chunk
    sched.commit(plan, None, step=2)
    assert a.state is RequestState.DECODING


def test_preemption_mid_prefill_restarts_from_token_zero():
    """Page pressure from an elder's decode growth preempts the youngest
    request while its chunked prefill is still mid-flight; the victim goes
    back to the queue front with prompt_pos reset to 0 (recompute-style:
    its whole decode state is rebuilt by re-prefilling on re-admission)."""
    kv = PagedKVCache(n_slots=2, max_len=32, page_size=8, page_budget=4)
    sched = Scheduler(kv, prefill_chunk=4)
    a = sched.submit(np.arange(1, 16), max_new_tokens=8)     # 15 tokens
    b = sched.submit(np.arange(1, 21), max_new_tokens=2)     # 20 tokens
    preempted_mid_prefill = False
    step = 0
    while a.state is not RequestState.FINISHED:
        was_prefilling = (b.state is RequestState.PREFILLING
                          and 0 < b.prompt_pos < b.prompt_len)
        plan = sched.next_plan(step)
        if was_prefilling and b.state is RequestState.QUEUED:
            preempted_mid_prefill = True
            assert b.prompt_pos == 0          # restart from token 0
            assert b.n_preemptions == 1
        sched.commit(plan, None, step)
        step += 1
        assert step < 100
    assert preempted_mid_prefill
    # victim is re-admitted and prefills its whole prompt again
    while b.state is not RequestState.FINISHED:
        plan = sched.next_plan(step)
        sched.commit(plan, None, step)
        step += 1
        assert step < 100
    assert b.finish_reason == "max_new_tokens"


def test_paged_cache_aux_state_accounting():
    """Per-slot aux (read-only context) pages are reserved at admission,
    never grow, and release with the slot — the vlm/audio cross-K/V
    footprint under an oversubscribed budget."""
    kv = PagedKVCache(n_slots=2, max_len=32, page_size=8,
                      slot_aux_tokens=20)           # 3 aux pages per slot
    assert kv.aux_pages_per_slot == 3
    assert kv.table.n_pages == 2 * (4 + 3)          # default full backing
    s0 = kv.admit(first_chunk=8)
    assert kv.table.n_used == 1 + 3
    assert kv.grow(s0, 32) and kv.table.n_used == 4 + 3
    kv.release(s0)
    assert kv.table.n_used == 0
    # a tight budget counts aux pages against admission
    kv = PagedKVCache(n_slots=2, max_len=32, page_size=8,
                      slot_aux_tokens=20, page_budget=4)
    assert kv.can_admit(8)                           # 1 + 3 aux = 4
    kv.admit(first_chunk=8)
    assert not kv.can_admit(8)


def test_scheduler_admits_queued_request_into_freed_slot():
    kv = PagedKVCache(n_slots=1, max_len=32, page_size=8)
    sched = Scheduler(kv, prefill_chunk=8)
    a = sched.submit(np.arange(1, 5), max_new_tokens=2)
    b = sched.submit(np.arange(1, 5), max_new_tokens=2)
    step = 0
    while a.state is not RequestState.FINISHED:
        plan = sched.next_plan(step)
        sched.commit(plan, None, step)
        step += 1
    assert b.state is RequestState.QUEUED
    plan = sched.next_plan(step)
    assert b.state is RequestState.PREFILLING
    assert b.slot == 0 and plan.reset_mask[0]   # recycled into a's slot
    assert b.admit_step > a.admit_step


# ---------------------------------------------------------------------------
# model cache API: slot reset + row extract/insert
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_model():
    cfg = reduced_config("granite-3-2b")
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    return cfg, model, params


def test_reset_cache_slots_zeroes_only_masked_rows(tiny_model):
    cfg, model, params = tiny_model
    B, S = 2, 8
    cache = model.init_cache(B, 16)
    tokens = jnp.ones((B, S), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    _, cache, _ = model.forward(params, tokens, pos, mode="prefill",
                                cache=cache)
    reset = model.reset_cache_slots(cache, jnp.array([True, False]))
    k = reset["layers"]["k"]                     # (n, B, S_cache, nkv, h)
    assert float(jnp.abs(k[:, 0]).max()) == 0.0
    assert float(jnp.abs(k[:, 1]).max()) > 0.0
    assert int(reset["layers"]["pos"][0, 0]) == 0
    assert int(reset["layers"]["pos"][0, 1]) == S


def test_cache_row_roundtrip(tiny_model):
    cfg, model, params = tiny_model
    cache = model.init_cache(3, 16)
    tokens = jnp.ones((3, 4), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(4)[None], (3, 4))
    _, cache, _ = model.forward(params, tokens, pos, mode="prefill",
                                cache=cache)
    row = model.cache_row(cache, 1)
    assert row["layers"]["k"].shape[1] == 1
    back = model.set_cache_row(cache, 1, row)
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool(jnp.array_equal(a, b)), back, cache))


# ---------------------------------------------------------------------------
# engine equivalence + continuous behavior
# ---------------------------------------------------------------------------
def test_continuous_greedy_matches_static_engine(tiny_model):
    cfg, model, params = tiny_model
    B, S, G = 3, 12, 8
    prompts = jax.random.randint(jax.random.key(1), (B, S), 1,
                                 cfg.vocab_size)
    static = StaticBatchEngine(model, params, max_len=48, batch=B)
    ref = np.asarray(static.generate(prompts, n_steps=G))
    eng = ContinuousBatchingEngine(model, params, n_slots=B, max_len=48,
                                   page_size=8, prefill_chunk=5)
    got = np.asarray(eng.generate(np.asarray(prompts), n_steps=G))
    np.testing.assert_array_equal(got, ref)


def test_midrun_admission_into_recycled_slot(tiny_model):
    cfg, model, params = tiny_model
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_len=48,
                                   page_size=8, prefill_chunk=6)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (9, 5, 7)]
    rids = [eng.submit(prompts[0], 4), eng.submit(prompts[1], 10),
            eng.submit(prompts[2], 4)]
    results = eng.run()
    reqs = {r.rid: r for r in eng.requests()}
    # third request waited for a slot, then entered mid-run
    assert reqs[rids[2]].admit_step > 0
    assert all(len(results[r]) == n for r, n in zip(rids, (4, 10, 4)))
    # each request's tokens match a solo single-slot run (per-sequence
    # isolation: other rows never leak into a slot's attention)
    for rid, prompt, g in zip(rids, prompts, (4, 10, 4)):
        solo = ContinuousBatchingEngine(model, params, n_slots=1,
                                        max_len=48, page_size=8,
                                        prefill_chunk=6)
        sr = solo.submit(prompt, g)
        np.testing.assert_array_equal(solo.run()[sr], results[rid])


def test_eos_finishes_request(tiny_model):
    cfg, model, params = tiny_model
    prompts = jax.random.randint(jax.random.key(1), (1, 12), 1,
                                 cfg.vocab_size)
    # find greedy token #2 first, then use it as the EOS id
    ref = ContinuousBatchingEngine(model, params, n_slots=1, max_len=48,
                                   page_size=8, prefill_chunk=6)
    ref_rid = ref.submit(np.asarray(prompts[0]), 6)
    eos = int(ref.run()[ref_rid][1])
    eng = ContinuousBatchingEngine(model, params, n_slots=1, max_len=48,
                                   page_size=8, prefill_chunk=6,
                                   eos_id=eos)
    rid = eng.submit(np.asarray(prompts[0]), 6)
    out = eng.run()
    assert eng.requests()[0].finish_reason == "eos"
    assert int(out[rid][-1]) == eos and len(out[rid]) == 2


def test_oversubscribed_pages_preempt_youngest_and_recover(tiny_model):
    cfg, model, params = tiny_model
    # budget of 3 pages cannot hold two 16-token prompts + decode growth:
    # the younger request is preempted (recompute-style), re-admitted
    # after the elder finishes, and both produce the solo-run tokens
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_len=32,
                                   page_size=8, page_budget=3)
    a = eng.submit(np.arange(1, 17), 4)
    b = eng.submit(np.arange(1, 17), 4)
    out = eng.run()
    assert sorted(r.n_preemptions for r in eng.requests()) == [0, 1]
    # throughput accounting counts only useful tokens: samples discarded
    # by the preemption (victim recomputed from token 0) don't inflate it
    assert eng.stats.generated_tokens == sum(len(t) for t in out.values())
    # a full drain returns every page (admission allocates atomically,
    # preemption/finish release symmetrically)
    assert eng.kv.table.n_used == 0 and eng.kv.n_active == 0
    solo = ContinuousBatchingEngine(model, params, n_slots=1, max_len=32,
                                    page_size=8)
    sr = solo.submit(np.arange(1, 17), 4)
    ref = solo.run()[sr]
    np.testing.assert_array_equal(out[a], ref)
    np.testing.assert_array_equal(out[b], ref)


def test_preempted_mid_prefill_request_recomputes_identically(tiny_model):
    """Engine-level twin of the scheduler mid-prefill preemption test:
    the same (budget, workload) shape preempts request b while its
    chunked prefill is mid-flight; after re-admission it must re-prefill
    from token 0 and emit exactly the tokens of an uncontended run."""
    cfg, model, params = tiny_model
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_len=32,
                                   page_size=8, page_budget=4,
                                   prefill_chunk=4)
    a = eng.submit(np.arange(1, 16), 8)          # 15 tokens, grows 3 pages
    b = eng.submit(np.arange(1, 21), 2)          # 20 tokens, chunked prefill
    out = eng.run()
    reqs = {r.rid: r for r in eng.requests()}
    assert reqs[b].n_preemptions >= 1
    solo = ContinuousBatchingEngine(model, params, n_slots=1, max_len=32,
                                    page_size=8, prefill_chunk=4)
    sb = solo.submit(np.arange(1, 21), 2)
    np.testing.assert_array_equal(solo.run()[sb], out[b])
    solo = ContinuousBatchingEngine(model, params, n_slots=1, max_len=32,
                                    page_size=8, prefill_chunk=4)
    sa = solo.submit(np.arange(1, 16), 8)
    np.testing.assert_array_equal(solo.run()[sa], out[a])


def test_many_finishes_never_alias_output_rows(tiny_model):
    # regression: >2*n_slots finishes between flushes used to double-free
    # output rows and interleave two requests' tokens in one buffer row
    cfg, model, params = tiny_model
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_len=32,
                                   page_size=8, prefill_chunk=4)
    rids = [eng.submit(np.arange(1, 5 + (i % 3)), 3) for i in range(12)]
    res = eng.run()
    for i, rid in enumerate(rids):
        solo = ContinuousBatchingEngine(model, params, n_slots=1,
                                        max_len=32, page_size=8,
                                        prefill_chunk=4)
        sr = solo.submit(np.arange(1, 5 + (i % 3)), 3)
        np.testing.assert_array_equal(solo.run()[sr], res[rid])


def test_same_step_prefill_sampling_decorrelated(tiny_model):
    cfg, model, params = tiny_model
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_len=32,
                                   page_size=8, prefill_chunk=8)
    r1 = eng.submit(np.arange(1, 9), 6, temperature=1.0)
    r2 = eng.submit(np.arange(1, 9), 6, temperature=1.0)
    out = eng.run()
    # identical prompts finishing prefill in the same step must not draw
    # identical noise
    assert out[r1].tolist() != out[r2].tolist()


def test_engine_accepts_recurrent_families():
    # the MIXED_STEP_FAMILIES gate is gone: every family with a
    # DecodeState adapter constructs (full parity coverage lives in
    # tests/test_serve_families.py)
    cfg = reduced_config("mamba2-780m")
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_len=32,
                                   page_size=8)
    assert eng.kv.slot_aux_tokens == 0


def test_engine_requires_context_extra_at_submit():
    cfg = reduced_config("whisper-base")
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    eng = ContinuousBatchingEngine(model, params, n_slots=1, max_len=32,
                                   page_size=8)
    # audio context pins aux pages for the slot's lifetime
    assert eng.kv.aux_pages_per_slot == -(-cfg.n_audio_ctx // 8)
    with pytest.raises(ValueError, match="audio_frames"):
        eng.submit(np.arange(1, 9), 4)
    # the static engine's batched (B, T, d) convention is rejected: an
    # install would silently clobber B consecutive slots' context
    batched = np.zeros((2, cfg.n_audio_ctx, cfg.d_model), np.float32)
    with pytest.raises(ValueError, match="per-request"):
        eng.submit(np.arange(1, 9), 4, extra={"audio_frames": batched})


def test_submit_validates_and_names_the_request():
    # malformed requests must explode at submit, naming the rid they
    # would have gotten — not steps later inside plan composition
    sched = Scheduler(PagedKVCache(2, 32, 8))
    with pytest.raises(ValueError, match=r"rid=0.*empty prompt"):
        sched.submit(np.array([], np.int64), 3)
    with pytest.raises(ValueError, match=r"rid=0.*max_new_tokens"):
        sched.submit(np.arange(1, 5), 0)
    with pytest.raises(ValueError, match=r"rid=0.*max_len"):
        sched.submit(np.arange(1, 30), 8)
    # a failed submit consumes no rid and queues nothing
    assert sched.next_rid == 0 and not sched.queue
    req = sched.submit(np.arange(1, 5), 2)
    assert req.rid == 0
    with pytest.raises(ValueError, match=r"rid=1.*must be >= 1"):
        sched.submit(np.arange(1, 5), -1)


def test_engine_stats_summary_zero_steps_is_total():
    # a zero-drain summary (engine built, nothing ran) must carry the
    # full key set with zeros — consumers index step_ms_p50 etc.
    # unconditionally and must never divide by an empty step list
    s = EngineStats().summary()
    for key in ("steps", "generated_tokens", "tok_per_s", "step_ms_p50",
                "step_ms_p95", "mean_occupancy", "mean_page_utilization",
                "prefix_hit_tokens", "prefix_hit_rate"):
        assert s[key] == 0
        assert not np.isnan(s[key])
    assert s["note"] == "zero steps executed"
