"""Compile (not run) the packed path's Pallas kernels for a TPU v5e.

Interpret mode accepts programs the chip's Mosaic compiler refuses
(unaligned block shapes, lane-splitting reshapes, scoped-VMEM overflow),
so every main-path kernel is compiled here at paper widths for a
described ``v5e:2x2`` topology and must come out as a ``tpu_custom_call``.
The topology is described inside a fixture, only once a test of this file
runs; the TPU compiler is loaded by whichever worker runs this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bitplane_pack import bitplane_pack
from repro.kernels.chain_conv import StageSpec, chain_conv
from repro.kernels.direct_conv_bn_binarize import direct_conv_bn_binarize
from repro.kernels.fused_conv_bn_binarize import fused_matmul_bn_binarize
from repro.kernels.mxu_pm1_matmul import mxu_pm1_matmul
from repro.kernels.xnor_popcount_matmul import xnor_popcount_matmul
from repro.runtime import regions

# YOLOv2-Tiny conv8 at 416^2: 13x13 positions, 1024 filters, 3x3x1024
# channels = 288 packed words per patch.
YOLO_CONV8 = dict(m=169, n=1024, w=288)
# AlexNet conv2: 27x27x96 input (3 words), 5x5 pad 2, 256 filters.
ALEX_CONV2 = dict(h=27, cw=3, k=5, pad=2, o=256)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


I32 = jnp.int32


def test_xnor_popcount_matmul_yolo_conv8(one_chip):
    m, n, w = YOLO_CONV8["m"], YOLO_CONV8["n"], YOLO_CONV8["w"]
    _compile(lambda a, b: xnor_popcount_matmul(a, b), one_chip,
             ((m, w), I32), ((n, w), I32))


def test_mxu_pm1_matmul_yolo_conv8(one_chip):
    m, n, w = YOLO_CONV8["m"], YOLO_CONV8["n"], YOLO_CONV8["w"]
    _compile(lambda a, b: mxu_pm1_matmul(a, b, k_valid=32 * w), one_chip,
             ((m, w), I32), ((n, w), I32))


def test_fused_conv_bn_binarize_alexnet_conv2(one_chip):
    c = ALEX_CONV2
    m, w = c["h"] * c["h"], c["k"] * c["k"] * c["cw"]
    _compile(lambda a, b, t, s: fused_matmul_bn_binarize(a, b, t, s),
             one_chip, ((m, w), I32), ((c["o"], w), I32),
             ((c["o"],), I32), ((c["o"],), I32))


@pytest.mark.parametrize("pool", [None, (3, 2)])
def test_direct_conv_bn_binarize_alexnet_conv2(one_chip, pool):
    c = ALEX_CONV2
    pool_kw = {} if pool is None else dict(pool_window=pool[0],
                                           pool_stride=pool[1])
    _compile(lambda x, w, t, s: direct_conv_bn_binarize(
                 x, w, t, s, kh=c["k"], kw=c["k"], pad=c["pad"], **pool_kw),
             one_chip, ((1, c["h"], c["h"], c["cw"]), I32),
             ((c["o"], c["k"] * c["k"] * c["cw"]), I32),
             ((c["o"],), I32), ((c["o"],), I32))


def test_chain_conv_yolo_first_region_416(one_chip):
    """conv1(+pool1) -> conv2(+pool2) on the 8 bit-plane words of a
    416x416 RGB frame: the first region the partitioner forms for
    YOLOv2-Tiny at its published resolution, at the tile it resolves."""
    stages = (StageSpec("conv", 3, 1, 1, 1, channels=16, first=True),
              StageSpec("pool", 2, 2, channels=16),
              StageSpec("conv", 3, 1, 1, 1, channels=32),
              StageSpec("pool", 2, 2, channels=32))
    in_shape = (1, 416, 416, 8)
    tiling = regions.plan_chain(stages, in_shape)
    assert tiling.tile["block_h"] < 104   # 416^2 is tiled into row bands

    def fn(x, w1, ww1, t1, s1, w2, t2, s2):
        return chain_conv(x, stages, (w1, ww1, t1, s1, w2, None, t2, s2),
                          **tiling.kernel_args())

    _compile(fn, one_chip, (in_shape, I32), ((16, 72), I32), ((72,), I32),
             ((16,), I32), ((16,), I32), ((32, 9), I32), ((32,), I32),
             ((32,), I32))


def test_bitplane_pack_416(one_chip):
    _compile(lambda x: bitplane_pack(x), one_chip,
             ((1, 416, 416, 3), jnp.uint8))
