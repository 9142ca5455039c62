"""Compiles for a described TPU v5e — what Mosaic would refuse on the chip.

The paged flash-decode kernel at granite-3-2b's served widths (8 slots,
32/8 heads, head_dim 64, 16-token pages, 1024-token rows, bf16) is
compiled, not run, for a v5e described by ``topologies``: decode
(Sq=1, block_pages 1, 8 and the whole 64-page row), the prefill chunk
(Sq=8, block_pages 8 and 64) and the
speculative verify width (Sq=5, spec_k=4), plus the ``shard_map``-wrapped
call the sharded engine makes on a 2x2 mesh.  Each compiled program must
hold the kernel (``tpu_custom_call``).  The dense model's paged decode
forward, two layers at these widths, must read and write the stacked KV
cache in place inside the layer loop: no slice, copy or write-back of a
layer's K/V there, and no second stack.

The topology is described only inside the module fixture (never at
import): one process at a time may load the TPU library, and where it
cannot be described the tests skip.
"""
import os

import pytest

pytestmark = pytest.mark.tier1

B, NQ, NKV, H, PAGE, MAX_LEN = 8, 32, 8, 64, 16, 1024


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # programs compiled for a described chip cannot be read back from
    # the persistent cache without one: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("sq,block_pages", [(1, 1), (1, 8), (1, 64), (8, 8),
                                            (8, 64), (5, 8)])
def test_paged_kernel_compiles_for_v5e(topo, sq, block_pages):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.kernels.paged_attention import ops as pa_ops

    one = SingleDeviceSharding(topo.devices[0])
    pps = MAX_LEN // PAGE
    pool = _sds((B * pps, PAGE, NKV, H), jnp.bfloat16, one)
    args = (_sds((B, sq, NQ, H), jnp.bfloat16, one), pool, pool,
            _sds((B, pps), jnp.int32, one), _sds((B, sq), jnp.int32, one),
            _sds((B,), jnp.int32, one))
    fn = jax.jit(lambda *a: pa_ops.paged_attention(
        *a, page_size=PAGE, block_pages=block_pages, impl="pallas",
        interpret=False))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_paged_attention_compiles_on_2x2(topo, monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.kernels.paged_attention import ops as pa_ops
    from repro.launch.mesh import AxisType, make_mesh
    from repro.models import attention
    from repro.parallel import axes as paxes
    from repro.parallel.sharding import rules_for

    # code that asks the backend sees this host's CPU: steer the kernel
    # off interpret mode, as it runs on the chip
    monkeypatch.setattr(pa_ops, "interpret_default", lambda i=None: False)
    mesh = make_mesh((2, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2, devices=topo.devices)
    rules = rules_for(get_config("granite-3-2b"), mesh)
    heads = NamedSharding(mesh, P("data", None, "model", None))
    stack = NamedSharding(mesh, P(None, "data", None, "model", None))
    rows = NamedSharding(mesh, P("data"))
    row_cols = NamedSharding(mesh, P("data", None))
    # a two-layer stacked cache, read at the scan's traced layer index
    cache = _sds((2, B, MAX_LEN, NKV, H), jnp.bfloat16, stack)
    args = (_sds((B, 1, NQ, H), jnp.bfloat16, heads), cache, cache,
            _sds((), jnp.int32, NamedSharding(mesh, P())),
            _sds((B, 1), jnp.int32, row_cols), _sds((B,), jnp.int32, rows),
            _sds((B, MAX_LEN // PAGE), jnp.int32, row_cols))

    def step(q, k, v, layer, positions, kv_valid, page_idx):
        # the engine's decode step: its page map split with the slots
        ps = attention.PagedDecodeState(page_idx=page_idx, page_size=PAGE,
                                        block_pages=8, impl="pallas")
        return attention._paged_attention_with_cache(
            q, k, v, ps, layer=layer, positions=positions,
            kv_valid_len=kv_valid, softcap=0.0)

    with paxes.sharding_ctx(mesh, rules):
        compiled = jax.jit(step).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # one kernel per shard: its acc partial is (slots 8/2, KV heads 8/2,
    # G*Sq 4, H) — slots split over "data", KV heads over "model"
    assert "f32[4,4,4,64]" in text


def _stack_sized_ops(text, shapes):
    """``{computation: [(opcode, name, called)]}`` for every instruction
    outside fusion bodies whose result has one of ``shapes``, other than
    parameters, tuple elements and bitcasts; ``called`` is the root
    opcode of a fusion's computation.  Also the names of the while
    loops' bodies."""
    import re

    head = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$")
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    bodies = set(re.findall(r"body=%([\w.\-]+)", text))
    roots, comp = {}, None
    for line in text.splitlines():
        if head.match(line):
            comp = head.match(line).group(1)
        elif line.lstrip().startswith("ROOT "):
            root = re.search(r"= [^ (]\S* ([\w\-]+)\(", line)
            roots[comp] = root and root.group(1)
    ops, comp = {}, None
    for line in text.splitlines():
        if head.match(line):
            comp = head.match(line).group(1)
            continue
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\w+\[[\d,]*\])\S* "
                     r"([\w\-]+)\(.*", line)
        if comp in fused or not m or m.group(2) not in shapes:
            continue
        if m.group(3) in ("parameter", "get-tuple-element", "tuple",
                          "bitcast"):
            continue
        called = re.search(r"calls=%([\w.\-]+)", line)
        ops.setdefault(comp, []).append(
            (m.group(3), m.group(1), called and roots.get(called.group(1))))
    return ops, bodies


@pytest.mark.parametrize("sq", [1, 5])
def test_dense_decode_reads_the_kv_stack_in_place(topo, monkeypatch, sq):
    """The decode forward (Sq=1) and the speculative verify (Sq=5) at the
    file's widths, two layers, the cache donated: inside the layer loop
    the step's tokens go into the carried stack by one scatter each for K
    and V and the kernel reads its pages there, with nothing else the
    size of a layer's or the stack's K/V (no slice, copy or write-back of
    a layer); no second stack is allocated, and both stacks alias their
    inputs.  The stack enters and leaves in the device's own layout, so
    converting it to the kernel's row-major pool, if the two differ, is
    left to the program's entry and exit."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.configs import get_config
    from repro.kernels.paged_attention import ops as pa_ops
    from repro.models import attention, build_model

    monkeypatch.setattr(pa_ops, "interpret_default", lambda i=None: False)
    L = 2
    model = build_model(get_config("granite-3-2b", n_layers=L))
    one = SingleDeviceSharding(topo.devices[0])

    def shapes(tree):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, one), tree)

    cache = shapes(jax.eval_shape(lambda: model.init_cache(B, MAX_LEN)))
    params = shapes(jax.eval_shape(model.init_params, jax.random.key(0)))
    ints = [_sds(s, jnp.int32, one)
            for s in ((B, sq), (B, sq), (B,), (B, MAX_LEN // PAGE))]

    def step(params, cache, tokens, positions, n_valid, page_idx):
        with attention.paged_decode(attention.PagedDecodeState(
                page_idx=page_idx, page_size=PAGE, block_pages=8,
                impl="pallas")):
            logits, cache, _ = model.forward(
                params, tokens, positions, mode="decode", cache=cache,
                n_valid=n_valid)
        return logits, cache

    text = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, *ints).compile().as_text()
    assert "tpu_custom_call" in text
    stack = f"bf16[{L},{B},{MAX_LEN},{NKV},{H}]"
    ops, bodies = _stack_sized_ops(text, {
        f"bf16[{B},{MAX_LEN},{NKV},{H}]", f"bf16[1,{B},{MAX_LEN},{NKV},{H}]",
        stack})
    assert len(bodies) == 1, bodies
    in_loop = ops.get(bodies.pop(), [])
    assert [c for _, _, c in in_loop] == ["scatter", "scatter"], in_loop
    assert not [line for line in text.splitlines()
                if "AllocateBuffer" in line and f"= {stack}{{" in line]
    # both stacks alias their donated inputs
    params_of = re.findall(
        r"%cache__layers____[kv]__\S* = " + re.escape(stack)
        + r"\S* parameter\((\d+)\)", text)
    alias = re.search(r"input_output_alias=\{(.*?) \}, ", text).group(1)
    assert len(params_of) == 2
    for n in params_of:
        assert f"({n}, {{}}, may-alias)" in alias, (n, alias)
