"""Every kernel dispatch can pick compiles for a TPU v5e.

Interpret mode (every other kernel test) runs the kernels' semantics on
the CPU, but never meets the chip compiler's layout, tiling and VMEM
rules.  Here each kernel is lowered with ``interpret=False`` for a
described ``v5e:2x2`` topology — nothing runs and no chip is needed —
at the widths the main path dispatches, with the edge block dispatch
would choose.  A compile that passes is not a chip run.

The topology is described inside a fixture only: describing it loads
the TPU library, which one process at a time may hold.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch
from repro.kernels.edge_mpnn import kernel as mpnn_kernel
from repro.kernels.segment_pool import kernel as seg_kernel


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compilation cache off:
    programs compiled for a described chip are written to it but can
    never be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel_compiles(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("variant,reduce,n,d", [
    ("onehot", "sum", 4096, 128),
    ("runs", "sum", 4096, 128),
    ("runs", "max", 4096, 128),
    # the one-hot max/min [E_blk, N, D] broadcast, at its envelope corner
    ("onehot", "max",
     dispatch.WORST_CASE_ENVELOPES["segment_pool:max"]["n_segments"],
     dispatch.WORST_CASE_ENVELOPES["segment_pool:max"]["d"]),
], ids=["onehot-sum", "runs-sum", "runs-max", "onehot-max-envelope"])
def test_segment_pool_compiles_for_v5e(one_chip, variant, reduce, n, d):
    e = 4 * n
    e_block = dispatch.choose_e_block(n, d, 4, reduce=reduce, n_edges=e,
                                      variant=variant)
    assert e_block
    fn = (seg_kernel.segment_pool_runs if variant == "runs"
          else seg_kernel.segment_pool)
    _assert_kernel_compiles(
        lambda v, s: fn(v, s, n_segments=n, reduce=reduce,
                        e_block=e_block, interpret=False),
        _spec(one_chip, (e, d)), _spec(one_chip, (e,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("variant", ["onehot", "runs"])
def test_edge_mpnn_compiles_for_v5e(one_chip, variant, dtype):
    n = 4096
    ds = dt = m = 128
    e = 4 * n
    itemsize = jnp.dtype(dtype).itemsize
    e_block = dispatch.choose_mpnn_e_block(n, n, ds, dt, m, itemsize,
                                           n_edges=e, variant=variant)
    assert e_block
    fn = (mpnn_kernel.edge_mpnn_runs if variant == "runs"
          else mpnn_kernel.edge_mpnn)
    _assert_kernel_compiles(
        lambda hs, ht, src, tgt, w, b: fn(
            hs, ht, src, tgt, w, b, n_src=n, n_tgt=n, e_block=e_block,
            interpret=False),
        _spec(one_chip, (n, ds), dtype), _spec(one_chip, (n, dt), dtype),
        _spec(one_chip, (e,), jnp.int32), _spec(one_chip, (e,), jnp.int32),
        _spec(one_chip, (ds + dt, m), dtype), _spec(one_chip, (m,), dtype))


def test_flash_graph_attention_compiles_for_v5e(one_chip):
    n, h, dh = 4096, 8, 128
    block = dispatch.choose_attention_block(n, h, dh, 4)
    assert block
    qkv = _spec(one_chip, (n, h, dh))
    _assert_kernel_compiles(
        lambda q, k, v, seg: dispatch._flash_graph_attention(
            q, k, v, seg, block=block, interpret=False),
        qkv, qkv, qkv, _spec(one_chip, (n,), jnp.int32))
