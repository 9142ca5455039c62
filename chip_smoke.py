#!/usr/bin/env python3
"""Chip smoke test: the serving path once, at granite-3-2b's full width,
on one TPU.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # the 2x2-mesh path only

One chip runs three phases in order, in this one process:

(a) device — the first device must be a TPU (exit 1 naming what JAX
    found otherwise);
(b) kernel — ``kernels/paged_attention`` through ``impl="pallas"`` against
    the float32 gather oracle (``ref.py``) at the served shapes, decode
    (Sq=1) and prefill-chunk (Sq=8) widths, at every ``block_pages``
    tile the engine's autotune may pick, ragged ``kv_valid`` with an
    empty row, a one-token row and a partial last page;
(c) serve — granite-3-2b from ``get_config`` (bf16, 40 layers, random
    weights from seed 0) in a ``ContinuousBatchingEngine`` of 8
    slots x 1024 tokens, 16 requests of 64-512 prompt tokens and 32 new
    tokens each (two sampled at temperature 0.7), drained with
    ``engine.run()``; the compiled decode and prefill programs must hold
    the Pallas kernel (``tpu_custom_call``), and the autotune's tile must
    be one that phase (b) checked.

``--four-chips`` serves granite-3-2b in float32 on a 2x2 mesh (slots over
"data", heads over "model") and on one chip, and requires the same
temperature-0 tokens from both, with parameters and KV pool spread over
all four devices.

The last line of stdout is ``{"ok": true, "device": {...}}``; any failed
check exits non-zero before it.  Timings are host walls that include
compilation, not device metrics.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.autotune import paged_block_candidates  # noqa: E402
from repro.core.compile_cache import use_compile_cache  # noqa: E402
from repro.kernels.paged_attention import ops as pa_ops  # noqa: E402
from repro.kernels.paged_attention import ref as pa_ref  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.perf.measure import now  # noqa: E402
from repro.serve import ContinuousBatchingEngine  # noqa: E402

ARCH = "granite-3-2b"
SEED = 0                   # random weights, requests and kernel inputs
N_SLOTS, PAGE, MAX_LEN, PREFILL_CHUNK = 8, 16, 1024, 8
N_REQUESTS, PROMPT_LENS, NEW_TOKENS = 16, (64, 512), 32
SAMPLED = (3, 11)          # the requests served at temperature 0.7
# max |pallas - fp32 oracle| on bf16 inputs: the output is rounded to
# bf16 (2^-9 relative on |out| < 4) and the MXU multiplies fp32 operands
# in bf16 passes; 3e-2 is a few times both
KERNEL_TOL = 3e-2


def check_device() -> jax.Device:
    """Phase (a): the first device must be a TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found platform "
                         f"{dev.platform!r} ({dev.device_kind})")
    print(f"[device] {dev.device_kind} x{len(jax.devices())}")
    return dev


def kernel_phase(*, batch: int, n_q_heads: int, n_kv_heads: int,
                 head_dim: int, page_size: int, max_len: int,
                 q_lens=(1, PREFILL_CHUNK), dtype=jnp.bfloat16,
                 seed: int = SEED):
    """Phase (b): the Pallas paged kernel against the fp32 oracle.

    One-page blocks stream a permuted page map; every tile the engine's
    autotune may pick (``paged_block_candidates`` at the widest query
    block here) streams the engine's identity map.  Returns the worst
    max-abs error and the tiles checked; raises when an error exceeds
    ``KERNEL_TOL``."""
    pps = max_len // page_size
    tiles = paged_block_candidates(
        pps=pps, page_size=page_size, n_kv_heads=n_kv_heads,
        head_dim=head_dim, rows=n_q_heads // n_kv_heads * max(q_lens),
        dtype_bytes=jnp.dtype(dtype).itemsize)
    key = jax.random.key(seed)
    kq, kk, kv, kp = jax.random.split(key, 4)
    pool = (batch * pps, page_size, n_kv_heads, head_dim)
    k_pages = jax.random.normal(kk, pool, jnp.float32).astype(dtype)
    v_pages = jax.random.normal(kv, pool, jnp.float32).astype(dtype)
    identity = jnp.arange(batch * pps, dtype=jnp.int32).reshape(batch, pps)
    permuted = jax.random.permutation(kp, batch * pps).astype(
        jnp.int32).reshape(batch, pps)
    # ragged lengths: empty, one token, a partial last page, full, ...
    edge = [0, 1, page_size + 3, max_len]
    valid = np.array([edge[b] if b < len(edge)
                      else (b * 37 + 5) % max_len for b in range(batch)],
                     np.int32)
    worst = 0.0
    for sq in q_lens:
        q = jax.random.normal(jax.random.fold_in(kq, sq),
                              (batch, sq, n_q_heads, head_dim),
                              jnp.float32).astype(dtype)
        pos = (np.maximum(valid - sq, 0)[:, None]
               + np.arange(sq, dtype=np.int32)[None])
        for bp, kind, page_idx in [(1, "permuted", permuted)] + [
                (t, "identity", identity) for t in tiles]:
            args = (q, k_pages, v_pages, page_idx, jnp.asarray(pos),
                    jnp.asarray(valid))
            out = pa_ops.paged_attention(*args, page_size=page_size,
                                         block_pages=bp, impl="pallas")
            want = pa_ref.paged_attention(
                q.astype(jnp.float32), *args[1:])
            err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)))
            print(f"[kernel] Sq={sq} block_pages={bp} {kind} map: "
                  f"max|err|={err!r} (bound {KERNEL_TOL})")
            if not np.isfinite(err) or err > KERNEL_TOL:
                raise AssertionError(
                    f"paged kernel Sq={sq} block_pages={bp} {kind} map: "
                    f"max|err| {err} exceeds {KERNEL_TOL}")
            worst = max(worst, err)
    return worst, tiles


def make_requests(vocab_size: int, *, n: int = N_REQUESTS,
                  prompt_lens=PROMPT_LENS, seed: int = SEED):
    """``n`` seeded (prompt, temperature) pairs."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        plen = int(rng.integers(prompt_lens[0], prompt_lens[1] + 1))
        prompt = rng.integers(1, vocab_size, size=plen).astype(np.int32)
        out.append((prompt, 0.7 if i in SAMPLED else 0.0))
    return out


def build_engine(cfg, *, seed: int = SEED, mesh=None,
                 n_slots: int = N_SLOTS, max_len: int = MAX_LEN,
                 page_size: int = PAGE,
                 prefill_chunk: int = PREFILL_CHUNK):
    """granite (or any dense ``cfg``) with seeded random weights, made on
    the device in one jitted init — on ``mesh`` directly in its layout."""
    model = build_model(cfg)
    init = jax.jit(model.init_params)
    if mesh is not None:
        from repro.parallel import axes as paxes
        from repro.parallel.sharding import rules_for
        shapes = jax.eval_shape(model.init_params, jax.random.key(seed))
        init = jax.jit(model.init_params, out_shardings=paxes.tree_shardings(
            model.param_specs(), shapes, mesh, rules_for(cfg, mesh)))
    params = init(jax.random.key(seed))
    return ContinuousBatchingEngine(
        model, params, n_slots=n_slots, max_len=max_len,
        page_size=page_size, prefill_chunk=prefill_chunk, seed=seed,
        mesh=mesh)


def serve(engine, requests, new_tokens: int = NEW_TOKENS):
    """Submit, drain, and check every request's token count and range.
    Returns ``{rid: tokens}`` and the drain wall (seconds, incl. compile)."""
    vocab = engine.model.cfg.vocab_size
    rids = [engine.submit(p, new_tokens, temperature=t) for p, t in requests]
    t0 = now()
    out = engine.run()
    wall = now() - t0
    for rid in rids:
        toks = np.asarray(out[rid])
        if toks.shape != (new_tokens,):
            raise AssertionError(f"request {rid}: {toks.shape[0]} tokens, "
                                 f"asked for {new_tokens}")
        if toks.min() < 0 or toks.max() >= vocab:
            raise AssertionError(f"request {rid}: token outside "
                                 f"[0, {vocab}): {toks.tolist()}")
    return {rid: np.asarray(out[rid]) for rid in rids}, wall


def serve_phase(cfg, *, seed: int = SEED, n_requests: int = N_REQUESTS,
                prompt_lens=PROMPT_LENS, new_tokens: int = NEW_TOKENS,
                **engine_kw):
    """Phase (c): build the engine, serve the seeded requests, drain.
    Returns the engine and its outputs."""
    engine = build_engine(cfg, seed=seed, **engine_kw)
    reqs = make_requests(cfg.vocab_size, n=n_requests,
                         prompt_lens=prompt_lens, seed=seed)
    out, wall = serve(engine, reqs, new_tokens)
    s = engine.stats.summary()
    print(f"[serve] {cfg.arch_id} {len(out)} requests: "
          f"{s['generated_tokens']} tokens in {wall!r} s wall incl. compile "
          f"= {s['generated_tokens'] / wall!r} tok/s (wall incl. compile); "
          f"steps={s['steps']}")
    pm = engine.paged_meta
    print(f"[serve] autotune: block_pages={pm['block_pages']} "
          f"impl={pm['impl']} source={pm['source']} key={pm['key']}")
    return engine, out


def compiled_text(engine) -> dict:
    """HLO text of the engine's compiled decode and prefill programs."""
    from repro.analysis.trace import serve_step_args
    sa = serve_step_args(engine)
    return {"decode": engine._decode_fn.lower(*sa["decode"]).compile()
            .as_text(),
            "prefill": engine._prefill_fn.lower(*sa["prefill"]).compile()
            .as_text()}


def four_chip_phase(cfg, *, mesh_spec: str = "2x2", seed: int = SEED,
                    n_requests: int = N_REQUESTS, prompt_lens=PROMPT_LENS,
                    new_tokens: int = NEW_TOKENS, **engine_kw) -> None:
    """The temperature-0 requests on one device, then on ``mesh_spec``:
    tokens must match, and params + KV pool must span every mesh device.
    The one-device engine is freed before the sharded one is built.

    Both run float32 matmuls at full precision: the TPU's default
    multiplies float32 in one bf16 pass, and for granite that also
    hoists a bf16 copy of every stacked weight out of the layer scan —
    3.75 GB that does not fit one chip beside 10 GB of float32 weights
    (v5e compile: 16.47 of 15.75 GB)."""
    from repro.launch.mesh import parse_mesh
    reqs = [(p, 0.0) for p, t in make_requests(
        cfg.vocab_size, n=n_requests, prompt_lens=prompt_lens, seed=seed)
        if t == 0.0]
    with jax.default_matmul_precision("highest"):
        engine = build_engine(cfg, seed=seed, **engine_kw)
        single, wall = serve(engine, reqs, new_tokens)
        print(f"[four-chips] one device: {len(single)} requests in "
              f"{wall!r} s wall incl. compile; block_pages="
              f"{engine.paged_meta['block_pages']}")
        del engine
        gc.collect()

        mesh = parse_mesh(mesh_spec)
        engine = build_engine(cfg, seed=seed, mesh=mesh, **engine_kw)
        want = set(mesh.devices.flat)
        for what, tree in (("params", engine.params),
                           ("kv pool", engine.cache)):
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
                held = leaf.sharding.device_set
                if held != want:
                    raise AssertionError(
                        f"{what} leaf {jax.tree_util.keystr(path)} on "
                        f"{len(held)} of {len(want)} mesh devices")
        sharded, wall = serve(engine, reqs, new_tokens)
    print(f"[four-chips] mesh {dict(mesh.shape)}: {len(sharded)} requests "
          f"in {wall!r} s wall incl. compile; "
          f"{engine.n_shards} slot shards")
    bad = [i for i, (a, b) in enumerate(zip(single.values(),
                                            sharded.values()))
           if not np.array_equal(a, b)]
    if bad:
        raise AssertionError(f"requests {bad}: mesh tokens differ from "
                             "one device")
    print(f"[four-chips] all {len(sharded)} temperature-0 requests match "
          "token for token")


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the float32 2x2-mesh vs one-chip check")
    args = ap.parse_args()
    dev = check_device()

    if args.four_chips:
        four_chip_phase(get_config(ARCH, param_dtype="float32",
                                   compute_dtype="float32"))
    else:
        cfg = get_config(ARCH)
        _, tiles = kernel_phase(
            batch=N_SLOTS, n_q_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim, page_size=PAGE, max_len=MAX_LEN)
        engine, _ = serve_phase(cfg)
        pm = engine.paged_meta
        if pm["impl"] != "pallas":
            raise AssertionError(f"paged impl {pm['impl']}")
        if pm["block_pages"] not in tiles:
            raise AssertionError(f"autotune picked block_pages="
                                 f"{pm['block_pages']}, not among the "
                                 f"tiles checked {tiles}")
        for name, text in compiled_text(engine).items():
            if "tpu_custom_call" not in text:
                raise AssertionError(f"compiled {name} program holds no "
                                     "Pallas kernel (tpu_custom_call)")
        print("[serve] tpu_custom_call in the compiled decode and prefill "
              "programs")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
