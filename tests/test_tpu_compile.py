"""The main path's Pallas kernels compile for a TPU v5e at the
paper-table3 widths: U = 40 users, d = 462 410 (the CIFAR-10 paper CNN,
viewed as [40, 3840, 128] rows), b = 10.

The chip is described, not attached (``jax.experimental.topologies``),
so these run on the CPU-only test machine and catch what Mosaic refuses
(block shapes, vector layouts, VMEM) without a chip.  A compile that
passes is not a chip run: nothing here executes.

The topology is described only inside the module fixture, never while
a module is imported: one process at a time may load the TPU library,
and pytest-xdist workers all import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import mixed_res as mr
from repro.kernels import quant_pack as qp
from repro.kernels.ops import sign_pad_len

U, D, B, LAM = 40, 462_410, 10, 0.2
W = sign_pad_len(D) // 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache off
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _shapes(sharding, shapes):
    return [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]


CPR = mr.code_words_per_row(B)
PLANES = [((U, W, 4), jnp.uint32), ((U, W, 4), jnp.uint32),
          ((U, W, CPR), jnp.uint32), ((U, 8), jnp.float32),
          ((U,), jnp.float32)]
KERNELS = {
    "mixed_res_reduce": (
        lambda x: mr.mixed_res_reduce(x, LAM, D),
        [((U, W, 128), jnp.float32)]),
    "mixed_res_emit": (
        lambda x, h: mr.mixed_res_emit(x, h, B, D),
        [((U, W, 128), jnp.float32), ((U, 8), jnp.float32)]),
    "mixed_res_dequant_reduce": (
        lambda *a: mr.mixed_res_dequant_reduce(*a, B),
        PLANES),
    "mixed_res_dequant_reduce_acc": (
        lambda s, h, c, hd, w, acc: mr.mixed_res_dequant_reduce(
            s, h, c, hd, w, B, acc=acc),
        PLANES + [((W, 128), jnp.float32)]),
    # the sign plane shares pack_lanes / unpack_lanes with the above
    "signpack": (qp.signpack, [((W, 128), jnp.float32)]),
    "sign_dequant_reduce": (
        qp.sign_dequant_reduce,
        [((U, W, 4), jnp.uint32), ((U,), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    compiled = jax.jit(fn).lower(*_shapes(one_chip, shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
