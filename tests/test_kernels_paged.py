"""Paged flash-decode kernel vs the dense gather oracle, plus the
engine-level contract: ragged edges (empty row, single token, exact page
boundary, last-page partial), GQA group sizes, block_pages tiling for
both impls, split-KV partial-combine associativity, and temperature-0
token parity of the paged engine against the XLA-gather baseline for
all five workload families.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import ops as pa_ops, ref as pa_ref

pytestmark = pytest.mark.tier1

PAGE = 8


def _pool(B, NQ, NKV, H, pps, *, sq=1, seed=0, permuted=False):
    """Random q + page pool with B*pps pages; identity or permuted map."""
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (B, sq, NQ, H), jnp.float32)
    kp = jax.random.normal(ks[1], (B * pps, PAGE, NKV, H), jnp.float32)
    vp = jax.random.normal(ks[2], (B * pps, PAGE, NKV, H), jnp.float32)
    if permuted:
        idx = jax.random.permutation(ks[3], B * pps)
        idx = idx.reshape(B, pps).astype(jnp.int32)
    else:
        idx = jnp.arange(B * pps, dtype=jnp.int32).reshape(B, pps)
    return q, kp, vp, idx


def _decode_positions(valid, sq):
    """Query positions for the last ``sq`` tokens of each row (the decode
    contract: kv_valid counts the in-flight queries, clamped NaN-safe for
    fully-masked rows)."""
    v = jnp.asarray(valid, jnp.int32)
    pos = v[:, None] - sq + jnp.arange(sq, dtype=jnp.int32)[None, :]
    return jnp.maximum(pos, 0)


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------
def test_pallas_ragged_permuted_pages():
    """Every ragged edge in one batch, on a *permuted* page map (the
    layout only the pallas page-walker supports): empty row, single
    token, exact page boundary, last-page partial, full cache."""
    B, NQ, NKV, H, pps = 5, 8, 2, 16, 4
    q, kp, vp, idx = _pool(B, NQ, NKV, H, pps, permuted=True, seed=3)
    valid = jnp.array([0, 1, 16, 27, 32], jnp.int32)
    positions = _decode_positions(valid, 1)
    got = pa_ops.paged_attention(q, kp, vp, idx, positions, valid,
                                 page_size=PAGE, impl="pallas",
                                 interpret=True)
    want = pa_ref.paged_attention(q, kp, vp, idx, positions, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # the empty row's contract: all-zero output, NaN-free
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_array_equal(np.asarray(got)[0], 0.0)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("group", [1, 4, 8])
def test_gqa_groups_multirow_queries(impl, group):
    """GQA head grouping (G queries per KV head) with Sq=4 in-flight
    query rows — head order must match the jnp.repeat expansion the
    oracle materializes."""
    B, NKV, H, pps, sq = 3, 2, 16, 4, 4
    NQ = NKV * group
    q, kp, vp, idx = _pool(B, NQ, NKV, H, pps, sq=sq, seed=group)
    valid = jnp.array([4, 19, 32], jnp.int32)
    positions = _decode_positions(valid, sq)
    got = pa_ops.paged_attention(q, kp, vp, idx, positions, valid,
                                 page_size=PAGE, impl=impl, interpret=True)
    want = pa_ref.paged_attention(q, kp, vp, idx, positions, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("block_pages", [1, 2, 4])
def test_block_pages_tiling_invariant(impl, block_pages):
    """The autotuned knob must never change the answer: every block_pages
    tiling matches the oracle on the identity layout."""
    B, NQ, NKV, H, pps = 4, 4, 2, 32, 4
    q, kp, vp, idx = _pool(B, NQ, NKV, H, pps, seed=11)
    valid = jnp.array([5, 8, 23, 32], jnp.int32)
    positions = _decode_positions(valid, 1)
    got = pa_ops.paged_attention(q, kp, vp, idx, positions, valid,
                                 page_size=PAGE, block_pages=block_pages,
                                 impl=impl, interpret=True)
    want = pa_ref.paged_attention(q, kp, vp, idx, positions, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_softcap_matches_oracle():
    B, NQ, NKV, H, pps = 2, 4, 2, 16, 4
    q, kp, vp, idx = _pool(B, NQ, NKV, H, pps, seed=5)
    valid = jnp.array([13, 32], jnp.int32)
    positions = _decode_positions(valid, 1)
    for impl in ("pallas", "xla"):
        got = pa_ops.paged_attention(q, kp, vp, idx, positions, valid,
                                     page_size=PAGE, softcap=30.0,
                                     impl=impl, interpret=True)
        want = pa_ref.paged_attention(q, kp, vp, idx, positions, valid,
                                      softcap=30.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_xla_impl_rejects_non_identity_pool():
    """The XLA specialization reshapes the pool as the dense cache — a
    pool that can't be the identity layout must fail loudly."""
    B, NQ, NKV, H, pps = 2, 4, 2, 16, 4
    q, kp, vp, idx = _pool(B, NQ, NKV, H, pps, seed=7)
    valid = jnp.array([8, 8], jnp.int32)
    positions = _decode_positions(valid, 1)
    extra = jnp.concatenate([kp, kp[:1]])       # pool != B * pps pages
    with pytest.raises(ValueError, match="identity"):
        pa_ops.paged_attention(q, extra, extra, idx, positions, valid,
                               page_size=PAGE, impl="xla")


# ---------------------------------------------------------------------------
# split-KV partials (the SP-KV combine contract)
# ---------------------------------------------------------------------------
def test_split_kv_partials_associative():
    """decode_partials over KV shards + combine_partials == the unsharded
    answer, and the combine is order-insensitive (exactly, not just
    allclose — the pmax/psum fold relies on it)."""
    B, sq, NQ, NKV, H, L = 3, 1, 8, 2, 16, 32
    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], (B, sq, NQ, H), jnp.float32)
    k = jax.random.normal(ks[1], (B, L, NKV, H), jnp.float32)
    v = jax.random.normal(ks[2], (B, L, NKV, H), jnp.float32)
    valid = jnp.array([3, 17, 32], jnp.int32)
    positions = _decode_positions(valid, sq)

    whole = pa_ops.combine_partials(
        [pa_ops.decode_partials(q, k, v, positions, valid)])
    half = L // 2
    p0 = pa_ops.decode_partials(q, k[:, :half], v[:, :half],
                                positions, valid)
    p1 = pa_ops.decode_partials(q, k[:, half:], v[:, half:],
                                positions, valid,
                                kv_offset=jnp.int32(half))
    fwd = pa_ops.combine_partials([p0, p1])
    rev = pa_ops.combine_partials([p1, p0])
    np.testing.assert_allclose(np.asarray(fwd), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(fwd), np.asarray(rev))


def test_return_partials_consistent_with_direct():
    """paged_attention(return_partials=True) fed through the combine must
    reproduce the direct normalized output, for both impls."""
    B, NQ, NKV, H, pps = 3, 4, 2, 16, 4
    q, kp, vp, idx = _pool(B, NQ, NKV, H, pps, seed=13)
    valid = jnp.array([2, 21, 32], jnp.int32)
    positions = _decode_positions(valid, 1)
    for impl in ("pallas", "xla"):
        direct = pa_ops.paged_attention(q, kp, vp, idx, positions, valid,
                                        page_size=PAGE, impl=impl,
                                        interpret=True)
        parts = pa_ops.paged_attention(q, kp, vp, idx, positions, valid,
                                       page_size=PAGE, impl=impl,
                                       interpret=True,
                                       return_partials=True)
        combined = pa_ops.combine_partials([parts], dtype=q.dtype)
        np.testing.assert_allclose(np.asarray(combined),
                                   np.asarray(direct),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# engine parity: paged kernel vs the XLA-gather decode, all families
# ---------------------------------------------------------------------------
FAMILY_ARCHS = [
    ("lm", "granite-3-2b"),
    ("ssm", "mamba2-780m"),
    ("hybrid", "jamba-v0.1-52b"),
    ("vlm", "llama-3.2-vision-90b"),
    ("audio", "whisper-base"),
]

REQUESTS = [(12, 5), (6, 4), (9, 3)]


@pytest.mark.parametrize("family,arch", FAMILY_ARCHS,
                         ids=[f for f, _ in FAMILY_ARCHS])
def test_paged_engine_matches_xla_token_for_token(family, arch):
    """Temperature-0 serving outputs must be token-identical with the
    paged kernel on (the engine default) and off (the dense XLA
    gather-then-attend decode) — per family, mixed prefill/decode."""
    from repro.configs import reduced_config
    from repro.models import build_model
    from repro.models.decode_state import stub_context
    from repro.serve import ContinuousBatchingEngine

    cfg = reduced_config(arch)
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, cfg.vocab_size, size=n)
               for n, _ in REQUESTS]
    extras = [stub_context(cfg, rng, scale=0.05) for _ in REQUESTS]

    outs = {}
    for paged in (True, False):
        eng = ContinuousBatchingEngine(
            model, params, n_slots=2, max_len=32, page_size=PAGE,
            prefill_chunk=4, paged_kernel=paged)
        assert eng.paged_kernel is paged
        rids = [eng.submit(p, g, extra=e)
                for p, (_, g), e in zip(prompts, REQUESTS, extras)]
        outs[paged] = {i: eng.run()[rid] for i, rid in enumerate(rids)}
    for i in outs[True]:
        np.testing.assert_array_equal(
            outs[True][i], outs[False][i],
            err_msg=f"{family}: paged/xla token divergence (request {i})")


# ---------------------------------------------------------------------------
# autotune: keyed by device kind, bounded by the kernel's VMEM footprint
# ---------------------------------------------------------------------------
_TUNE = dict(n_slots=2, max_len=64, page_size=8, n_kv_heads=1, n_q_heads=4,
             head_dim=16, dtype="float32", reps=1)


def test_autotune_row_of_another_device_never_steers(tmp_path):
    import json

    from repro.core import autotune

    path = tmp_path / "tune.json"
    info = autotune.tune_paged_attention(cache_path=path, **_TUNE)
    kind = jax.devices()[0].device_kind
    assert info["source"] == "measured"
    assert info["key"].endswith(f"/{info['impl']}/{kind}")
    assert set(info["medians_s"]) == {"bp1", "bp2", "bp4", "bp8"}
    # the same row, relabelled as measured on a chip: it must not be read
    payload = json.loads(path.read_text())
    (row,) = payload["rows"]
    row["key"] = row["key"][:-len(kind)] + "TPU v5 lite"
    path.write_text(json.dumps(payload))
    assert autotune.tune_paged_attention(
        cache_path=path, **_TUNE)["source"] == "measured"
    assert autotune.tune_paged_attention(
        cache_path=path, **_TUNE)["source"] == "cache"


def test_autotune_sweeps_only_tiles_that_fit_vmem(tmp_path, monkeypatch):
    from repro.core import autotune
    from repro.kernels.paged_attention import kernel as pa_kernel

    def footprint(bp):
        # 8 query columns (a prefill chunk) x group 4 rows per KV head
        return pa_kernel.vmem_bytes(block_pages=bp, page_size=8,
                                    n_kv_heads=1, head_dim=16, rows=4 * 8,
                                    dtype_bytes=4)

    assert footprint(1) < footprint(2) < footprint(4)
    # a chip whose scoped VMEM holds exactly the 2-page tile
    monkeypatch.setattr(autotune, "SCOPED_VMEM_BYTES", footprint(2))
    info = autotune.tune_paged_attention(
        cache_path=tmp_path / "tune.json", max_q_len=8, **_TUNE)
    assert set(info["medians_s"]) == {"bp1", "bp2"}
    assert info["block_pages"] in (1, 2)
    monkeypatch.setattr(autotune, "SCOPED_VMEM_BYTES", footprint(1) - 1)
    with pytest.raises(ValueError, match="no block_pages candidate fits"):
        autotune.tune_paged_attention(
            cache_path=tmp_path / "tune.json", max_q_len=8, **_TUNE)


# ---------------------------------------------------------------------------
# sharded call: the page map splits with the slots and is rebased per shard
# ---------------------------------------------------------------------------
_SHARDED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from repro.configs import reduced_config
from repro.launch.mesh import AxisType, make_mesh
from repro.models import attention
from repro.parallel import axes as paxes
from repro.parallel.sharding import rules_for

B, NQ, NKV, H, PAGE, PPS = 4, 8, 2, 32, 8, 4
ks = jax.random.split(jax.random.key(0), 3)
q = jax.random.normal(ks[0], (B, 1, NQ, H), jnp.float32)
# a two-layer stacked cache: the call reads layer 1's pages from the stack
k = jax.random.normal(ks[1], (2, B, PPS * PAGE, NKV, H), jnp.float32)
v = jax.random.normal(ks[2], (2, B, PPS * PAGE, NKV, H), jnp.float32)
# two slot rows per "data" shard; each row's pages are permuted within
# its own shard's block of the pool
rng = np.random.default_rng(0)
idx = np.concatenate([s * 2 * PPS + rng.permutation(2 * PPS)
                      for s in range(2)]).reshape(B, PPS).astype(np.int32)
valid = np.array([0, 1, PAGE + 3, PPS * PAGE], np.int32)
pos = np.maximum(valid - 1, 0)[:, None].astype(np.int32)
ps = attention.PagedDecodeState(page_idx=jnp.asarray(idx), page_size=PAGE,
                                block_pages=2, impl="pallas")


def step(q, k, v, positions, kv_valid):
    return attention._paged_attention_with_cache(
        q, k, v, ps, layer=1, positions=positions, kv_valid_len=kv_valid,
        softcap=0.0)


# the kernel over layer 1's own pool
from repro.kernels.paged_attention import ops as pa_ops
want = pa_ops.paged_attention(
    q, k[1].reshape(B * PPS, PAGE, NKV, H),
    v[1].reshape(B * PPS, PAGE, NKV, H), jnp.asarray(idx), pos, valid,
    page_size=PAGE, block_pages=2, impl="pallas")
np.testing.assert_allclose(np.asarray(jax.jit(step)(q, k, v, pos, valid)),
                           np.asarray(want), atol=1e-5)
mesh = make_mesh((2, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
cfg = reduced_config("granite-3-2b", n_heads=NQ, n_kv_heads=NKV)
# a fresh function: jit's trace cache does not key on the sharding context
with paxes.sharding_ctx(mesh, rules_for(cfg, mesh)):
    got = jax.jit(lambda *a: step(*a))(q, k, v, pos, valid)
assert len(got.sharding.device_set) == 4, got.sharding
np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
print("SHARDED_PAGE_MAP_OK")
"""


def test_sharded_call_follows_the_page_map():
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SHARDED], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED_PAGE_MAP_OK" in out.stdout, (
        out.stdout[-2000:] + out.stderr[-4000:])
