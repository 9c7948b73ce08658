"""Compile (not run) the packed path's Pallas kernels for a TPU v5e.

Interpret mode accepts programs the chip's Mosaic compiler refuses
(unaligned block shapes, lane-splitting reshapes, scoped-VMEM overflow),
so every main-path kernel is compiled here at paper widths for a
described ``v5e:2x2`` topology and must come out as a ``tpu_custom_call``.
The topology is described inside a fixture, only once a test of this file
runs; the TPU compiler is loaded by whichever worker runs this file.
"""

import itertools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.layer_integration import IntegratedParams
from repro.kernels.bitplane_pack import bitplane_pack
from repro.kernels.chain_conv import StageSpec, chain_conv
from repro.kernels.direct_conv_bn_binarize import direct_conv_bn_binarize
from repro.kernels.fused_conv_bn_binarize import fused_matmul_bn_binarize
from repro.kernels.image_conv import column_group, image_conv_bn_binarize
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


def _compile(fn, sharding, *shapes, name):
    """Compile for the described chip; the kernel must come out as a
    custom call named ``name`` (its name on a device trace)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{name}(\.\d+)? = \S+ custom-call\(", text), name


I32 = jnp.int32


def test_xnor_popcount_matmul_yolo_conv8(one_chip):
    m, n, w = YOLO_CONV8["m"], YOLO_CONV8["n"], YOLO_CONV8["w"]
    _compile(lambda a, b: xnor_popcount_matmul(a, b), one_chip,
             ((m, w), I32), ((n, w), I32), name="xnor_matmul")


def test_mxu_pm1_matmul_yolo_conv8(one_chip):
    m, n, w = YOLO_CONV8["m"], YOLO_CONV8["n"], YOLO_CONV8["w"]
    _compile(lambda a, b: mxu_pm1_matmul(a, b, k_valid=32 * w), one_chip,
             ((m, w), I32), ((n, w), I32), name="pm1_matmul")


def test_fused_conv_bn_binarize_alexnet_conv2(one_chip):
    c = ALEX_CONV2
    m, w = c["h"] * c["h"], c["k"] * c["k"] * c["cw"]
    _compile(lambda a, b, t, s: fused_matmul_bn_binarize(a, b, t, s),
             one_chip, ((m, w), I32), ((c["o"], w), I32),
             ((c["o"],), I32), ((c["o"],), I32), name="matmul_bn_binarize")


@pytest.mark.parametrize("pool", [None, (3, 2)])
def test_direct_conv_bn_binarize_alexnet_conv2(one_chip, pool):
    c = ALEX_CONV2
    pool_kw = {} if pool is None else dict(pool_window=pool[0],
                                           pool_stride=pool[1])
    _compile(lambda x, w, t, s: direct_conv_bn_binarize(
                 x, w, t, s, kh=c["k"], kw=c["k"], pad=c["pad"], **pool_kw),
             one_chip, ((1, c["h"], c["h"], c["cw"]), I32),
             ((c["o"], c["k"] * c["k"] * c["cw"]), I32),
             ((c["o"],), I32), ((c["o"],), I32),
             name="direct_conv" if pool is None else "direct_conv_pool")


def test_chain_conv_yolo_first_region_416(one_chip):
    """conv2(+pool2) -> conv3(+pool3) -> conv4(+pool4) on the packed
    208x208 conv1 output of a 416x416 frame: the first region the
    partitioner forms for YOLOv2-Tiny at its published resolution (conv1
    runs on the MXU, outside every chain), at the tile it resolves."""
    stages = (StageSpec("conv", 3, 1, 1, 1, channels=32),
              StageSpec("pool", 2, 2, channels=32),
              StageSpec("conv", 3, 1, 1, 1, channels=64),
              StageSpec("pool", 2, 2, channels=64),
              StageSpec("conv", 3, 1, 1, 1, channels=128),
              StageSpec("pool", 2, 2, channels=128))
    in_shape = (1, 208, 208, 1)
    tiling = regions.plan_chain(stages, in_shape)
    assert tiling.tile["block_h"] < 26    # 208^2 is tiled into row bands

    def fn(x, w1, t1, s1, w2, t2, s2, w3, t3, s3):
        return chain_conv(x, stages, (w1, None, t1, s1, w2, None, t2, s2,
                                      w3, None, t3, s3),
                          **tiling.kernel_args())

    _compile(fn, one_chip, (in_shape, I32), ((32, 9), I32), ((32,), I32),
             ((32,), I32), ((64, 9), I32), ((64,), I32), ((64,), I32),
             ((128, 18), I32), ((128,), I32), ((128,), I32),
             name="chain_region")


@pytest.fixture(scope="module")
def vgg16_regions():
    """The VGG16 conv stack at 224^2 lowered as the engine lowers it, from
    parameter shapes alone, and the regions the partitioner forms at
    bucket 8."""
    from repro.core import bnn_model, converter
    from repro.core.bnn_model import BConv, Pool
    from repro.models import paper_nets
    from repro.runtime import graph as rgraph
    from repro.runtime import passes

    spec, (h, w, _) = paper_nets.get("vgg16")
    spec = list(itertools.takewhile(lambda l: isinstance(l, (BConv, Pool)),
                                    spec))
    params = jax.eval_shape(lambda k: bnn_model.init_params(k, spec),
                            jax.random.key(0))
    packed = jax.eval_shape(lambda p: converter.convert(p, spec, (h, w)),
                            params)
    g = passes.fuse_pool_epilogue(rgraph.lower_packed(spec, packed, (h, w)))
    return g, regions.partition_chains(g, (8, h, w, 3))


def test_vgg16_regions_224(vgg16_regions):
    _, chains = vgg16_regions
    assert [c.node_ids for c in chains] == [(4, 5, 7, 8, 9),
                                            (11, 12, 13, 15, 16, 17, 19)]


@pytest.mark.parametrize("k", [0, 1])
def test_chain_conv_vgg16_regions_224(one_chip, vgg16_regions, k):
    """Each region the partitioner forms for VGG16 at bucket 8 (conv1_2
    through conv3_2 entering at 224^2, conv3_3 through conv5_3 entering at
    56^2: two full-resolution convs back to back) compiles at the tile
    ``plan_chain`` resolves."""
    g, chains = vgg16_regions
    chain = chains[k]
    arrays = regions.chain_stage_arrays(
        chain, {str(n): g.nodes[n].params for n in chain.node_ids})
    present = [a is not None for a in arrays]
    kw = chain.tiling().kernel_args()

    def fn(x, *given):
        it = iter(given)
        arrs = tuple(next(it) if p else None for p in present)
        return chain_conv(x, chain.stages, arrs, **kw)

    _compile(fn, one_chip, (chain.in_shape, I32),
             *[(a.shape, a.dtype) for a in arrays if a is not None],
             name="chain_region")


@pytest.mark.parametrize("shape,k,stride,pad,o,pool", [
    ((8, 227, 227, 3), 11, 4, 0, 96, (3, 2, (0, 0))),   # AlexNet conv1
    ((1, 416, 416, 3), 3, 1, 1, 16, (2, 2, (0, 0))),    # YOLOv2-Tiny conv1
    ((8, 224, 224, 3), 3, 1, 1, 64, None),              # VGG16 conv1_1
])
def test_image_conv_first_layers(one_chip, shape, k, stride, pad, o, pool):
    """The first binary conv on the MXU, straight from the uint8 image,
    as the two served first layers lower: one XLA convolution (no Pallas
    kernel) with the threshold, pool and pack after it."""
    group = column_group(o, (shape[2] + 2 * pad - k) // stride + 1)
    span = stride * group
    blocks = -(-(stride * (group - 1) + k) // span)

    def fn(x, w, c1, t, s):
        return image_conv_bn_binarize(x, w, c1, IntegratedParams(t, s),
                                      kernel=k, stride=stride, pad=pad,
                                      pool=pool)

    args = [jax.ShapeDtypeStruct(sh, d, sharding=one_chip) for sh, d in (
        (shape, jnp.uint8), ((k, blocks, span * 3, group * o), jnp.bfloat16),
        ((o,), I32), ((o,), I32), ((o,), jnp.bool_))]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert re.search(r"= \S+ convolution\(", text)
    assert "custom-call" not in text


def test_bitplane_pack_416(one_chip):
    _compile(lambda x: bitplane_pack(x), one_chip,
             ((1, 416, 416, 3), jnp.uint8), name="bitplane_pack")
