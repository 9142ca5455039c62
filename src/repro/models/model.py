"""The unified LM covering all 10 assigned architectures.

``LM(cfg)`` builds init/forward/cache machinery from a ``ModelConfig``:

  family   stack
  -------  -----------------------------------------------------------
  dense    scan over N identical (attn + MLP) layers
  moe      scan over N identical (attn + MoE) layers
  ssm      scan over N Mamba-2 SSD blocks (no FFN; d_ff = 0)
  hybrid   scan over N/8 Jamba periods (1 attn : 7 mamba, MoE alternating)
  vlm      scan over N/5 periods (4 self-attn + 1 gated cross-attn layer)
  audio    whisper enc-dec: encoder scan + decoder scan (self + cross)

Modes: ``train`` (logits over full seq), ``prefill`` (logits + populated
cache), ``decode`` (1-token step against the cache).  ``extra`` carries stub
frontend embeddings: ``image_embeds`` (B, T_img, d) for vlm,
``audio_frames`` (B, n_audio_ctx, d) for audio.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, blocks, decode_state, layers
from repro.models.layers import dtype_of
from repro.parallel.axes import constrain

Params = Dict[str, Any]


def _tree_stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _tree_index(tree, i):
    return jax.tree.map(lambda t: t[i], tree)


class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        if cfg.family == "hybrid":
            assert cfg.n_layers % cfg.attn_period == 0
            self.n_periods = cfg.n_layers // cfg.attn_period
        elif cfg.family == "vlm":
            assert cfg.n_layers % cfg.cross_attn_period == 0
            self.n_periods = cfg.n_layers // cfg.cross_attn_period
        else:
            self.n_periods = cfg.n_layers
        # the family's DecodeState adapter: cache layout + specs + the
        # admission-time context install (serving engine contract)
        self.decode_state = decode_state.get_adapter(cfg.family)

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------
    def init_params(self, key) -> Params:
        cfg = self.cfg
        ke, ku, ks, kenc = jax.random.split(key, 4)
        dtype = dtype_of(cfg.param_dtype)
        p: Params = {
            "embed": layers.init_embedding(ke, cfg.padded_vocab, cfg.d_model,
                                           dtype),
            "final_norm": layers.init_rmsnorm(cfg.d_model, dtype),
        }
        if not cfg.tie_embeddings:
            p["unembed"] = layers.init_embedding(ku, cfg.padded_vocab,
                                                 cfg.d_model, dtype)
        p["stack"] = blocks.stack_init(ks, self.n_periods, self._init_period)
        if cfg.is_encdec:
            p["encoder"] = {
                "stack": blocks.stack_init(
                    kenc, cfg.n_encoder_layers,
                    lambda k: blocks.init_attn_layer(k, cfg, use_moe=False)),
                "final_norm": layers.init_rmsnorm(cfg.d_model, dtype),
            }
        return p

    def _init_period(self, key) -> Params:
        cfg = self.cfg
        fam = cfg.family
        if fam in ("dense", "moe"):
            return blocks.init_attn_layer(key, cfg, use_moe=cfg.layer_uses_moe(0))
        if fam == "ssm":
            return blocks.init_mamba_layer(key, cfg, with_ffn=cfg.d_ff > 0)
        if fam == "hybrid":
            ks = jax.random.split(key, cfg.attn_period)
            subs = {}
            for j in range(cfg.attn_period):
                use_moe = cfg.layer_uses_moe(j)
                if cfg.layer_kind(j) == "attn":
                    subs[f"s{j}"] = blocks.init_attn_layer(ks[j], cfg, use_moe)
                else:
                    subs[f"s{j}"] = blocks.init_mamba_layer(ks[j], cfg, use_moe)
            return subs
        if fam == "vlm":
            per = cfg.cross_attn_period
            ks = jax.random.split(key, per)
            subs = {
                f"s{j}": blocks.init_attn_layer(ks[j], cfg, use_moe=False)
                for j in range(per - 1)
            }
            subs["cross"] = blocks.init_cross_layer(ks[-1], cfg)
            return subs
        if fam == "audio":
            k1, k2, k3 = jax.random.split(key, 3)
            p = blocks.init_attn_layer(k1, cfg, use_moe=False)
            p["lnx"] = layers.init_rmsnorm(cfg.d_model, dtype_of(cfg.param_dtype))
            p["xattn"] = attention.init_attention(k3, cfg, cross=False)
            return p
        raise ValueError(fam)

    def param_specs(self) -> Params:
        cfg = self.cfg
        p: Params = {
            "embed": layers.embedding_specs(),
            "final_norm": layers.rmsnorm_specs(),
        }
        if not cfg.tie_embeddings:
            p["unembed"] = layers.embedding_specs()
        p["stack"] = blocks.stack_specs(self._period_specs())
        if cfg.is_encdec:
            p["encoder"] = {
                "stack": blocks.stack_specs(
                    blocks.attn_layer_specs(cfg, use_moe=False)),
                "final_norm": layers.rmsnorm_specs(),
            }
        return p

    def _period_specs(self) -> Params:
        cfg = self.cfg
        fam = cfg.family
        if fam in ("dense", "moe"):
            return blocks.attn_layer_specs(cfg, use_moe=cfg.layer_uses_moe(0))
        if fam == "ssm":
            return blocks.mamba_layer_specs(cfg, with_ffn=cfg.d_ff > 0)
        if fam == "hybrid":
            subs = {}
            for j in range(cfg.attn_period):
                use_moe = cfg.layer_uses_moe(j)
                if cfg.layer_kind(j) == "attn":
                    subs[f"s{j}"] = blocks.attn_layer_specs(cfg, use_moe)
                else:
                    subs[f"s{j}"] = blocks.mamba_layer_specs(cfg, use_moe)
            return subs
        if fam == "vlm":
            per = cfg.cross_attn_period
            subs = {
                f"s{j}": blocks.attn_layer_specs(cfg, use_moe=False)
                for j in range(per - 1)
            }
            subs["cross"] = blocks.cross_layer_specs(cfg)
            return subs
        if fam == "audio":
            p = blocks.attn_layer_specs(cfg, use_moe=False)
            p["lnx"] = layers.rmsnorm_specs()
            p["xattn"] = attention.attention_specs(cfg, cross=False)
            return p
        raise ValueError(fam)

    # ------------------------------------------------------------------
    # cache (DecodeState protocol — family enters only via the adapter)
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int) -> Params:
        return self.decode_state.init(self, batch, max_len)

    def cache_specs(self) -> Params:
        return self.decode_state.specs(self)

    def cache_row(self, cache: Params, slot) -> Params:
        """Extract batch row ``slot`` of the cache as a batch-1 cache —
        the read half of the paged cache's slot-indexed update.
        jit-compatible (``slot`` may be traced)."""
        return decode_state.state_row(cache, self.cache_specs(), slot)

    def set_cache_row(self, cache: Params, slot, row: Params) -> Params:
        """Write a batch-1 cache back into batch row ``slot`` (the write
        half of the slot-indexed update)."""
        return decode_state.set_state_row(cache, self.cache_specs(), slot,
                                          row)

    def reset_cache_slots(self, cache: Params, slot_mask: jax.Array) -> Params:
        """Zero the cache rows (KV entries, positions, recurrent state,
        installed context) of the batch slots selected by ``slot_mask``
        (B,) bool — the slot-recycling primitive of the paged serving
        cache.  jit-compatible: the batch axis of every leaf is located
        via ``cache_specs()``."""
        return decode_state.reset_state_slots(cache, self.cache_specs(),
                                              slot_mask)

    def adjust_cache_counters(self, cache: Params, delta) -> Params:
        """Subtract per-slot ``delta`` (B,) from the cache's position
        counters — the speculative-decode rewind to the accepted
        frontier (``decode_state.adjust_state_counters``; only valid
        for ``decode_state.token_addressable`` families).
        jit-compatible (``delta`` may be traced)."""
        return decode_state.adjust_state_counters(cache, self.cache_specs(),
                                                  delta)

    def install_cache_prefix(self, cache: Params, src_slot, dst_slot,
                             n_tokens) -> Params:
        """Copy the first ``n_tokens`` token entries of ``src_slot``'s KV
        rows into ``dst_slot`` and set its position counters to
        ``n_tokens`` — the device half of serve prefix caching (only
        valid for ``decode_state.prefix_cachable`` families).
        jit-compatible; ``src_slot == dst_slot`` trims in place."""
        return decode_state.copy_state_prefix(cache, self.cache_specs(),
                                              src_slot, dst_slot, n_tokens)

    def install_slot_context(self, params: Params, cache: Params, slot,
                             extra: Dict[str, jax.Array]) -> Params:
        """Admission-time write of a request's read-only context state
        (cross-attention K/V from image embeddings / encoder output) into
        its slot's cache row.  A no-op tree-copy for families without
        such state; jit-compatible (``slot`` may be traced)."""
        row = self.cache_row(cache, slot)
        row = self.decode_state.install_context(self, params, row, extra)
        return self.set_cache_row(cache, slot, row)

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------
    def forward(
        self,
        params: Params,
        tokens: jax.Array,              # (B, S) int32
        positions: jax.Array,           # (B, S) int32
        *,
        mode: str = "train",
        cache: Optional[Params] = None,
        extra: Optional[Dict[str, jax.Array]] = None,
        n_valid: Optional[jax.Array] = None,   # (B,) decode-mode ragged rows
    ) -> Tuple[jax.Array, Optional[Params], jax.Array]:
        cfg = self.cfg
        # under the serve engine's paged-decode context the lookup runs
        # gather-free (one-hot matmul, bitwise-identical) so the decode
        # program clears the trace linter's hot-gather rule
        x = layers.embed(tokens, params["embed"], dtype_of(cfg.compute_dtype),
                         one_hot=attention.paged_state() is not None)
        x = constrain(x, "batch", None, None)

        ctx = None
        if cfg.family == "vlm" and mode != "decode":
            ctx = extra["image_embeds"].astype(x.dtype)
        if cfg.family == "audio":
            enc_aux = jnp.zeros((), jnp.float32)
            if mode != "decode":
                ctx, enc_aux = self.encode_audio(
                    params, extra["audio_frames"].astype(x.dtype))

        step = functools.partial(
            self._period_step, mode=mode, positions=positions, ctx=ctx,
            n_valid=n_valid)
        stacked_cache = None
        if cache is not None:
            stacked_cache = cache.get("layers") or cache.get("periods")

        x, new_stacked, aux = blocks.run_stack(
            x, params["stack"], step, stacked_cache=stacked_cache,
            n_steps=self.n_periods, remat=cfg.remat if mode == "train" else "none")

        if cfg.family == "audio" and mode != "decode":
            aux = aux + enc_aux

        x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
        emb = params["embed"] if cfg.tie_embeddings else params["unembed"]
        logits = layers.unembed(x, emb)
        logits = constrain(logits, "batch", None, "vocab")

        logits = logits.astype(jnp.float32)
        if cfg.padded_vocab != cfg.vocab_size:
            # the table's padding rows are not tokens: never predict one
            logits = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab_size,
                               logits, -1e30)

        new_cache = None
        if cache is not None:
            key = "layers" if "layers" in cache else "periods"
            new_cache = {key: new_stacked}
        return logits, new_cache, aux

    # ------------------------------------------------------------------
    def encode_audio(self, params: Params, frames: jax.Array
                     ) -> Tuple[jax.Array, jax.Array]:
        """Run the whisper encoder over (B, n_audio_ctx, d) frame
        embeddings; returns (encoder output, aux loss).  Used by the
        train/prefill forward and by the audio DecodeState adapter's
        admission-time context install."""
        cfg = self.cfg
        enc = frames.astype(dtype_of(cfg.compute_dtype))
        enc_pos = jnp.broadcast_to(
            jnp.arange(enc.shape[1])[None], enc.shape[:2])

        def enc_step(h, p, _c):
            return blocks.attn_layer(
                p, h, cfg, mode="train", positions=enc_pos, causal=False)

        enc, _, enc_aux = blocks.run_stack(
            enc, params["encoder"]["stack"], enc_step,
            n_steps=cfg.n_encoder_layers, remat=cfg.remat)
        enc = layers.rms_norm(enc, params["encoder"]["final_norm"],
                              cfg.norm_eps)
        return enc, enc_aux

    # ------------------------------------------------------------------
    def _period_step(self, x, p, c, *, mode, positions, ctx, n_valid=None):
        """One scan step: a single layer (homogeneous) or one period."""
        cfg = self.cfg
        fam = cfg.family
        zero = jnp.zeros((), jnp.float32)

        if fam in ("dense", "moe"):
            x, nc, aux = blocks.attn_layer(
                p, x, cfg, mode=mode, positions=positions,
                cache=c if mode != "train" else None, n_valid=n_valid)
            return x, nc, aux

        if fam == "ssm":
            x, ns, aux = blocks.mamba_layer(p, x, cfg, mode=mode, state=c,
                                            n_valid=n_valid)
            return x, ns, aux

        if fam == "hybrid":
            aux = zero
            new_attn, new_ssm = None, []
            midx = 0
            for j in range(cfg.attn_period):
                sub = p[f"s{j}"]
                if cfg.layer_kind(j) == "attn":
                    x, new_attn, a = blocks.attn_layer(
                        sub, x, cfg, mode=mode, positions=positions,
                        cache=c["attn"] if mode != "train" else None,
                        n_valid=n_valid)
                else:
                    st = (_tree_index(c["ssm"], midx)
                          if mode == "decode" else None)
                    x, ns, a = blocks.mamba_layer(sub, x, cfg, mode=mode,
                                                  state=st, n_valid=n_valid)
                    new_ssm.append(ns)
                    midx += 1
                aux = aux + a
            nc = None
            if mode != "train":
                nc = {"attn": new_attn, "ssm": _tree_stack(new_ssm)}
            return x, nc, aux

        if fam == "vlm":
            aux = zero
            per = cfg.cross_attn_period
            new_self = []
            for j in range(per - 1):
                sc = (_tree_index(c["self"], j) if mode != "train" else None)
                x, ns, a = blocks.attn_layer(
                    p[f"s{j}"], x, cfg, mode=mode, positions=positions,
                    cache=sc, n_valid=n_valid)
                new_self.append(ns)
                aux = aux + a
            if mode == "decode":
                x, _, a = blocks.cross_layer(
                    p["cross"], x, cfg,
                    cached_kv=(c["cross_k"], c["cross_v"]))
                kv = (c["cross_k"], c["cross_v"])
            else:
                x, kv, a = blocks.cross_layer(p["cross"], x, cfg, ctx=ctx)
            aux = aux + a
            nc = None
            if mode != "train":
                nc = {"self": _tree_stack(new_self),
                      "cross_k": kv[0], "cross_v": kv[1]}
            return x, nc, aux

        if fam == "audio":
            # decoder layer: self-attn + cross-attn + mlp
            h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
            if mode == "train":
                a_out = attention.attn_train(p["attn"], h, cfg,
                                             positions=positions)
                new_self = None
            elif mode == "prefill":
                a_out, new_self = attention.attn_prefill(
                    p["attn"], h, cfg, positions=positions, cache=c["self"])
            else:
                a_out, new_self = attention.attn_decode(
                    p["attn"], h, cfg, positions=positions, cache=c["self"],
                    n_valid=n_valid)
            x = x + a_out
            h = layers.rms_norm(x, p["lnx"], cfg.norm_eps)
            if mode == "decode":
                xa, _ = attention.cross_attn(
                    p["xattn"], h, cfg,
                    cached_kv=(c["cross_k"], c["cross_v"]))
                kv = (c["cross_k"], c["cross_v"])
            else:
                xa, kv = attention.cross_attn(p["xattn"], h, cfg, ctx=ctx)
            x = x + xa
            h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
            f, aux = blocks._mlp_or_moe(p, h, cfg)
            x = x + f
            nc = None
            if mode != "train":
                nc = {"self": new_self, "cross_k": kv[0], "cross_v": kv[1]}
            return x, nc, aux

        raise ValueError(fam)


def build_model(cfg: ModelConfig) -> LM:
    return LM(cfg)
