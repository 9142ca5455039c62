"""The one spelling of the JAX seams the repo calls (jax 0.9.0, see
requirements-dev.txt); mesh construction lives in ``repro.launch.mesh``.
The source lint's ``compat-shim-bypass`` rule keeps every call site here:

  cost_dict   ``Compiled.cost_analysis()`` as one flat dict (``{}`` when
              the backend reports nothing).
  shard_map   ``jax.shard_map`` with the checking switch named ``check``.
"""
from __future__ import annotations

from typing import Any, Dict

import jax


def cost_dict(compiled) -> Dict[str, Any]:
    """``compiled.cost_analysis()`` normalized to one flat dict.

    Returns the entry for the main module when the backend reports a
    per-module list, and ``{}`` when the backend reports nothing.
    """
    cost = compiled.cost_analysis()
    if cost is None:
        return {}
    if isinstance(cost, (list, tuple)):
        return dict(cost[0]) if cost else {}
    return dict(cost)


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = True):
    """``jax.shard_map``; ``check`` keeps upstream's checking default
    (VMA validation on) — callers that need it off opt out explicitly."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
