"""Production mesh construction + mesh-API compat shims.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state.  The single-pod mesh is
16x16 = 256 chips (one TPU v5e pod); multi-pod adds a leading "pod" axis
(2 pods = 512 chips).  Data parallelism maps to ("pod", "data"), tensor/
expert parallelism to "model" (see repro.parallel).

``make_mesh`` (with ``AxisType`` re-exported) is the one mesh entry
point: every mesh in the repo is built through it, which the source
lint's ``compat-shim-bypass`` rule enforces.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              axis_types: Optional[Sequence] = None,
              devices=None) -> Mesh:
    """``jax.make_mesh`` over ``devices`` (default: all) with optional
    ``axis_types``."""
    kwargs = {}
    if devices is not None:
        kwargs["devices"] = devices
    if axis_types is not None:
        kwargs["axis_types"] = tuple(axis_types)
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names), **kwargs)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; have {len(devices)} — "
            "launch via repro.launch.dryrun (it sets "
            "--xla_force_host_platform_device_count before importing jax)")
    return make_mesh(shape, axes,
                     axis_types=(AxisType.Auto,) * len(axes),
                     devices=devices[:n])


def parse_mesh(spec: Optional[str]) -> Optional[Mesh]:
    """CLI mesh spec -> Mesh (or None for the single-device no-op path).

    ``"2"`` -> (data=2); ``"2x4"`` -> (data=2, model=4);
    ``"2x4x4"`` -> (pod=2, data=4, model=4) — axis names follow the
    production layout so the default sharding rules (slot axis over
    ("pod", "data"), tensor/SP-KV over "model") apply unchanged.
    ``None`` / ``""`` / ``"none"`` / ``"1"`` select no mesh: serving
    stays on the single-device path (a strict no-op, not a 1-device
    mesh).
    """
    if spec is None or spec.lower() in ("", "none", "1"):
        return None
    try:
        dims = tuple(int(d) for d in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"bad mesh spec {spec!r}: want N, NxM, or NxMxK")
    names = {1: ("data",), 2: ("data", "model"),
             3: ("pod", "data", "model")}.get(len(dims))
    if names is None or any(d < 1 for d in dims):
        raise ValueError(f"bad mesh spec {spec!r}: want N, NxM, or NxMxK")
    n = math.prod(dims)
    if n > len(jax.devices()):
        raise RuntimeError(
            f"mesh {spec} needs {n} devices; have {len(jax.devices())} — "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            "before jax is imported to fake them on CPU")
    return make_mesh(dims, names, axis_types=(AxisType.Auto,) * len(dims))


def make_host_mesh(model: int = 1) -> Mesh:
    """A small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = n // model
    return make_mesh((data, model), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))
