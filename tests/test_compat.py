"""The JAX seams in repro.core.compat + repro.launch.mesh:
cost_analysis normalization, mesh construction, shard_map."""
import jax
import jax.numpy as jnp
import pytest

from repro.core import compat
from repro.launch.mesh import AxisType, make_mesh

pytestmark = pytest.mark.tier1


class _FakeCompiled:
    def __init__(self, ret):
        self._ret = ret

    def cost_analysis(self):
        return self._ret


def test_cost_dict_normalizes_every_return_shape():
    assert compat.cost_dict(_FakeCompiled(None)) == {}
    assert compat.cost_dict(_FakeCompiled([])) == {}
    assert compat.cost_dict(_FakeCompiled({"flops": 4.0})) == {"flops": 4.0}
    assert compat.cost_dict(
        _FakeCompiled([{"flops": 8.0}])) == {"flops": 8.0}
    assert compat.cost_dict(
        _FakeCompiled(({"bytes accessed": 2.0},))) == {"bytes accessed": 2.0}


def test_cost_dict_on_real_compiled():
    compiled = jax.jit(lambda x: x @ x).lower(
        jnp.ones((8, 8), jnp.float32)).compile()
    cost = compat.cost_dict(compiled)
    assert isinstance(cost, dict)
    assert cost.get("flops", 0.0) > 0.0


def test_make_mesh_accepts_axis_types():
    mesh = make_mesh((1,), ("x",), axis_types=(AxisType.Auto,))
    assert mesh.shape == {"x": 1}
    mesh2 = make_mesh((1, 1), ("a", "b"))
    assert tuple(mesh2.axis_names) == ("a", "b")


def test_shard_map_compat_runs():
    mesh = make_mesh((1,), ("x",), axis_types=(AxisType.Auto,))
    from jax.sharding import PartitionSpec as P

    fn = compat.shard_map(lambda a: a * 2, mesh=mesh, in_specs=(P("x"),),
                          out_specs=P("x"), check=False)
    out = fn(jnp.arange(4, dtype=jnp.float32))
    assert out.tolist() == [0.0, 2.0, 4.0, 6.0]
