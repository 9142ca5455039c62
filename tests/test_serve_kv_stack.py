"""The stacked KV cache, carried through the layer scan and used in place.

Dense and moe models keep one KV cache per layer, stacked (L, B, T, NKV,
H).  ``blocks.run_stack`` carries that stack through the layer scan;
``attention.attn_decode`` scatters each step's tokens into it at
``[layer, row, pos + j]`` and the paged kernel reads the layer's pages
from the whole stack viewed as one pool.  Every other cache (ssm, hybrid,
vlm, audio) is still scanned one layer slice at a time.

On the CPU the paged engine runs the kernel's XLA twin, so the Pallas
kernel's reading of the stacked pool is checked here in interpret mode,
at the model level.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import reduced_config
from repro.models import attention, build_model
from repro.models.decode_state import stub_context
from repro.serve import ContinuousBatchingEngine

pytestmark = pytest.mark.tier1

ARCHS = {"dense": "granite-3-2b", "moe": "phi3.5-moe-42b-a6.6b",
         "hybrid": "jamba-v0.1-52b", "vlm": "llama-3.2-vision-90b"}
PAGE = 8


def _model(family):
    cfg = reduced_config(ARCHS[family])
    assert cfg.family == family
    model = build_model(cfg)
    return cfg, model, model.init_params(jax.random.key(0))


def _scan_carry_avals(model, params, cache, tokens):
    """Avals of the layer scan's carry in the decode forward's jaxpr."""
    positions = jnp.zeros(tokens.shape, jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, c: model.forward(
        p, tokens, positions, mode="decode", cache=c))(params, cache)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    eqn = scans[0]
    n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
    return [v.aval for v in eqn.invars[n_consts:n_consts + n_carry]]


# ---------------------------------------------------------------------------
# tokens pinned from before the stack was carried: every family serves the
# same greedy tokens, and only dense/moe carry their cache
# ---------------------------------------------------------------------------
REQUESTS = [(9, 6), (6, 5), (5, 4)]
PINNED = {
    "dense": [[96, 423, 318, 318, 293, 465], [83, 303, 4, 264, 481],
              [226, 202, 59, 396]],
    "moe": [[336, 125, 103, 419, 19, 271], [204, 171, 376, 62, 171],
            [381, 193, 381, 177]],
    "hybrid": [[280, 491, 164, 215, 163, 39], [161, 16, 354, 114, 195],
               [442, 500, 507, 9]],
    "vlm": [[484, 484, 484, 484, 393, 78], [42, 263, 503, 503, 503],
            [346, 34, 64, 34]],
}


@pytest.mark.parametrize("family", list(PINNED))
def test_family_keeps_its_tokens_and_scan_path(family):
    cfg, model, params = _model(family)
    cache = model.init_cache(2, 32)
    carry = _scan_carry_avals(model, params, cache,
                              jnp.zeros((2, 1), jnp.int32))
    stacks = [a for a in carry if a.ndim == 5]
    if family in ("dense", "moe"):
        # x, aux, and the whole K and V stacks
        assert len(carry) == 4
        assert [a.shape for a in stacks] == [cache["layers"]["k"].shape] * 2
    else:
        assert len(carry) == 2 and not stacks
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n, _ in REQUESTS]
    extras = [stub_context(cfg, rng, scale=0.05) for _ in REQUESTS]
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_len=32,
                                   page_size=PAGE, prefill_chunk=4)
    rids = [eng.submit(p, g, extra=e)
            for p, (_, g), e in zip(prompts, REQUESTS, extras)]
    out = eng.run()
    assert [out[r].tolist() for r in rids] == PINNED[family]


# ---------------------------------------------------------------------------
# the paged engine against its XLA twin: tokens and the final stack
# ---------------------------------------------------------------------------
def _serve(model, params, prompts, gens, **kw):
    eng = ContinuousBatchingEngine(model, params, n_slots=3, max_len=48,
                                   page_size=PAGE, prefill_chunk=8, **kw)
    rids = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    decode_rows, prefill_rows = set(), set()
    while eng.step():
        plan = eng.last_plan
        if plan is not None:
            decode_rows.update(int(n) for n in plan.n_valid)
            prefill_rows.update(plan.prefill_chunks.values())
    out = eng.run()
    return eng, [out[r] for r in rids], decode_rows, prefill_rows


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
@pytest.mark.parametrize("family", ["dense", "moe"])
def test_paged_engine_matches_xla_twin_in_tokens_and_stack(family, spec):
    cfg, model, params = _model(family)
    rng = np.random.default_rng(11)
    # repeating prompts give the n-gram drafter something to propose
    prompts = [np.tile(rng.integers(1, cfg.vocab_size, size=3), n)
               for n in (5, 3, 2, 4)]
    gens = (7, 9, 4, 6)
    kw = dict(spec_decode=True, spec_k=3) if spec else {}
    paged, want, rows, chunks = _serve(model, params, prompts, gens, **kw)
    twin, got, _, _ = _serve(model, params, prompts, gens,
                             paged_kernel=False, **kw)
    # idle rows (0), decode rows (1) and full prefill chunks all ran
    assert {0, 1} <= rows and 8 in chunks
    if spec:
        assert max(rows) > 1, "no drafted tokens were verified"
        assert paged.stats.accepted_draft_tokens > 0
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(paged.cache["layers"]["pos"],
                                  twin.cache["layers"]["pos"])
    for leaf in ("k", "v"):
        np.testing.assert_allclose(np.asarray(paged.cache["layers"][leaf]),
                                   np.asarray(twin.cache["layers"][leaf]),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_preemption_and_prefix_install_round_trip(family):
    """Shared prefixes on an oversubscribed budget: a preempted request
    re-admits and prefix hits are copied between slots of the stack, and
    the tokens equal a cold, roomy run's."""
    cfg, model, params = _model(family)
    rng = np.random.default_rng(4)
    shared = rng.integers(1, cfg.vocab_size, size=14)
    prompts = [np.concatenate([shared, rng.integers(1, cfg.vocab_size,
                                                    size=n)])
               for n in (1, 2, 3)]
    gens = (4, 3, 3)

    def run(**kw):
        eng = ContinuousBatchingEngine(model, params, n_slots=2,
                                       max_len=32, page_size=PAGE,
                                       prefill_chunk=4, **kw)
        rids = [eng.submit(p, g) for p, g in zip(prompts, gens)]
        out = eng.run()
        return eng, [out[r] for r in rids]

    warm, got = run(page_budget=4, prefix_cache=True)
    _, want = run()
    assert sum(r.n_preemptions for r in warm.requests()) >= 1
    assert warm.sched.prefix_hit_tokens > 0
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# the Pallas kernel (interpret mode) reading each layer from the stack
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("family", ["dense", "moe"])
def test_pallas_kernel_reads_each_layer_from_the_stack(family):
    """Decode steps of mixed width and ragged ``n_valid`` through the
    Pallas kernel over the stacked pool equal the plain XLA attention
    over each layer, in logits and in the whole final stack."""
    cfg, model, params = _model(family)
    assert cfg.n_layers > 1
    B, T = 2, 32
    pps = T // PAGE
    page_idx = jnp.arange(B * pps, dtype=jnp.int32).reshape(B, pps)
    rng = np.random.default_rng(5)
    # (width, n_valid per row): a chunk with one idle row, then ragged
    # decode rows, then a verify-width step
    steps = [(8, [8, 0]), (1, [1, 1]), (1, [0, 1]), (5, [3, 5])]

    def run(paged):
        cache = model.init_cache(B, T)
        logits = []
        for width, n_valid in steps:
            pos0 = np.asarray(cache["layers"]["pos"][0])
            tokens = jnp.asarray(rng.integers(1, cfg.vocab_size,
                                              size=(B, width)), jnp.int32)
            positions = jnp.asarray(pos0[:, None] + np.arange(width),
                                    jnp.int32)
            nv = jnp.asarray(n_valid, jnp.int32)

            def fwd(params, cache, tokens, positions, nv):
                return model.forward(params, tokens, positions,
                                     mode="decode", cache=cache, n_valid=nv)

            if paged:
                ps = attention.PagedDecodeState(
                    page_idx=page_idx, page_size=PAGE, block_pages=2,
                    impl="pallas")

                def fwd(params, cache, tokens, positions, nv, _f=fwd):
                    with attention.paged_decode(ps):
                        return _f(params, cache, tokens, positions, nv)

            lg, cache, _ = jax.jit(fwd)(params, cache, tokens, positions, nv)
            logits.append(np.asarray(lg))
        return logits, cache

    state = rng.bit_generator.state
    want, want_cache = run(paged=False)
    rng.bit_generator.state = state
    got, got_cache = run(paged=True)
    for (width, n_valid), a, b in zip(steps, want, got):
        for row, n in enumerate(n_valid):
            np.testing.assert_allclose(a[row, :n], b[row, :n],
                                       atol=2e-4, rtol=2e-4)
    np.testing.assert_array_equal(want_cache["layers"]["pos"],
                                  got_cache["layers"]["pos"])
    for leaf in ("k", "v"):
        np.testing.assert_allclose(np.asarray(want_cache["layers"][leaf]),
                                   np.asarray(got_cache["layers"][leaf]),
                                   atol=2e-4, rtol=2e-4)
