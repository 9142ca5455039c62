"""Per-architecture smoke tests: reduced same-family configs, one forward
(train) step + prefill/decode on CPU; asserts shapes and finiteness.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, reduced_config
from repro.models import build_model


def _extra(cfg, batch):
    extra = {}
    if cfg.family == "vlm":
        extra["image_embeds"] = jnp.ones(
            (batch, cfg.num_image_tokens, cfg.d_model), jnp.float32) * 0.01
    if cfg.family == "audio":
        extra["audio_frames"] = jnp.ones(
            (batch, cfg.n_audio_ctx, cfg.d_model), jnp.float32) * 0.01
    return extra


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_forward(arch):
    cfg = reduced_config(arch)
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    B, S = 2, 32
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    logits, cache, aux = model.forward(
        params, tokens, positions, mode="train", extra=_extra(cfg, B))
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert cache is None
    assert np.isfinite(np.asarray(logits)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_then_decode_matches_full_forward(arch):
    """Teacher-forced decode after prefill must match the train forward
    logits position-by-position (the KV-cache/state correctness invariant)."""
    cfg = reduced_config(arch)
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    B, S_p, S_total, max_len = 2, 8, 12, 16
    tokens = jax.random.randint(jax.random.key(1), (B, S_total), 0,
                                cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S_total)[None], (B, S_total))
    extra = _extra(cfg, B)

    full_logits, _, _ = model.forward(params, tokens, positions,
                                      mode="train", extra=extra)

    cache = model.init_cache(B, max_len)
    pre_logits, cache, _ = model.forward(
        params, tokens[:, :S_p], positions[:, :S_p], mode="prefill",
        cache=cache, extra=extra)
    np.testing.assert_allclose(
        np.asarray(pre_logits), np.asarray(full_logits[:, :S_p]),
        rtol=2e-4, atol=2e-4)

    logits_steps = [pre_logits[:, -1:]]
    for t in range(S_p, S_total):
        step_logits, cache, _ = model.forward(
            params, tokens[:, t : t + 1], positions[:, t : t + 1],
            mode="decode", cache=cache, extra=extra)
        logits_steps.append(step_logits)

    for i, t in enumerate(range(S_p, S_total)):
        np.testing.assert_allclose(
            np.asarray(logits_steps[i + 1][:, 0]),
            np.asarray(full_logits[:, t]),
            rtol=2e-4, atol=2e-4,
            err_msg=f"{arch}: decode step at position {t} diverges",
        )


def test_param_count_plausible():
    # full configs: analytic parameter count sanity (grok ~314B, llama-v ~88B)
    from repro.configs import get_config
    total, active = get_config("grok-1-314b").param_counts()
    assert 280e9 < total < 340e9, total
    assert active < total
    t2, a2 = get_config("phi3.5-moe-42b-a6.6b").param_counts()
    assert 38e9 < t2 < 46e9, t2
    assert 5.5e9 < a2 < 8.5e9, a2
    t3, _ = get_config("mamba2-780m").param_counts()
    assert 0.6e9 < t3 < 0.95e9, t3


def test_padded_vocab_rows_are_never_predicted():
    # published vocabularies pad the table (granite: 49155 -> 49408
    # rows); the padding rows are no token, so their logits are masked
    cfg = reduced_config("granite-3-2b", vocab_size=500)
    assert cfg.padded_vocab == 512
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(8), (2, 8))
    logits, _, _ = model.forward(params, tokens, positions)
    assert logits.shape[-1] == 512
    assert bool(jnp.all(logits[..., cfg.vocab_size:] <= -1e29))
    assert bool(jnp.all(jnp.isfinite(logits[..., :cfg.vocab_size])))
    assert int(jnp.argmax(logits, -1).max()) < cfg.vocab_size
