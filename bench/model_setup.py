"""From a configuration file to a serving engine with seeded weights.

The file holds the published config.json keys as run.  Those that name a
``ModelConfig`` field (``_FIELDS``) are mapped onto it; the file's
``program`` group names the program's architecture (``arch``), the fields
it sets beyond the published keys (``set``: family, MLP type and the
like), and the published keys that the program runs only at one value
(``requires``), which refuses a file that states another rather than run
it as something else.  The weights are the benchmark's: made from the
seed on the device, in the served dtype, in one jitted call, in the
layout of the program's parameter tree (which ``jax.eval_shape`` reads
without running the program's own initialiser).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import registry

# config.json key -> ModelConfig field
_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def reference(c: dict, root=registry.ROOT):
    """The configuration's plain reference, ``bench/reference/<name>.py``."""
    return registry.module("reference", c["reference"], root)


def model_config(c: dict):
    """The program's ``ModelConfig`` for configuration file ``c``."""
    from repro.configs import get_config
    prog = c["program"]
    for key, want in prog.get("requires", {}).items():
        if key in c and c[key] != want:
            raise ValueError(f"{key}={c[key]!r}: the program runs only {want!r}")
    over = {f: c[k] for k, f in _FIELDS.items() if k in c}
    if "num_attention_heads" in c:
        over["head_dim"] = head_dim(c)
    over.update(param_dtype=c["torch_dtype"], compute_dtype=c["torch_dtype"])
    over.update(prog.get("set", {}))
    return get_config(prog["arch"], **over)


def seed_key(seed: int):
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _leaf(key, path: str, shape, dtype):
    """Norm scales near 1, embedding rows at 0.02, projections at
    fan_in**-0.5: the scales of a trained model, from the seed."""
    if path.endswith("['scale']"):
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if path.endswith("['table']"):
        return 0.02 * jax.random.normal(key, shape, dtype)
    if path.endswith("['w']"):
        return jax.random.normal(key, shape, dtype) * jnp.asarray(
            shape[-2] ** -0.5, dtype)
    raise ValueError(f"no initialiser for parameter {path}")


def init_weights(model, seed: int, leaf=_leaf):
    """The parameter tree, made on the device from ``seed``; ``leaf``
    makes one leaf (a reference may bring its own for leaves that
    ``_leaf`` does not know)."""
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        return jax.tree_util.tree_unflatten(treedef, [
            leaf(jax.random.fold_in(key, i), jax.tree_util.keystr(p),
                 s.shape, s.dtype) for i, (p, s) in enumerate(flat)])

    return jax.jit(make)(seed_key(seed))


def build_engine(c: dict, seed: int, root=registry.ROOT):
    """(engine, params) for configuration file ``c`` and ``seed``."""
    from repro.models import build_model
    from repro.serve import ContinuousBatchingEngine
    model = build_model(model_config(c))
    params = init_weights(model, seed,
                          getattr(reference(c, root), "init_leaf", _leaf))
    # the engine's own seed keys sampling only (every request here is
    # greedy) and is baked into its programs: keep it fixed so that every
    # seed finds them in the compile cache
    engine = ContinuousBatchingEngine(model, params, seed=0, **c["engine"])
    return engine, params
