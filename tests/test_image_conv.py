"""The first binary conv as one exact integer conv on the MXU, straight
from the uint8 image (``kernels/image_conv.py``, executor backend
``mxu_image``): bit-exact with the bit-plane ``xla`` oracle on the served
first-layer shapes, the exactness guard, and whole graphs under the modes
that take it (no bit-plane expansion runs, the first conv heads no chain)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import runtime, workloads
from repro.core import bnn_model, converter, packing
from repro.core.bnn_model import BConv, Pool
from repro.kernels import image_conv
from repro.runtime import GraphExecutor, lower_packed
from repro.runtime.graph import image_conv_expand


def _randomize_bn(params, seed):
    """BN statistics that put the first layer's thresholds inside the range
    of its sums, so both bit values occur."""
    rng = np.random.default_rng(seed)
    for p in params:
        if "mu" in p:
            o = p["mu"].shape[0]
            half = 255 * p["w"].size // o // 4
            p["mu"] = jnp.asarray(rng.uniform(-half, half, o), jnp.float32)
            p["var"] = jnp.asarray(rng.uniform(0.5, 4, o), jnp.float32)
            p["gamma"] = jnp.asarray(rng.uniform(-1.5, 1.5, o), jnp.float32)
            p["beta"] = jnp.asarray(rng.uniform(-1, 1, o), jnp.float32)
    return params


def _first_layer_graph(spec, hw):
    params = _randomize_bn(bnn_model.init_params(jax.random.key(5), spec),
                           seed=3)
    packed = converter.convert(params, spec, hw)
    return runtime.fuse_pool_epilogue(lower_packed(spec, packed, hw))


def _frames(kind, n, hw):
    if kind == "zeros":
        return jnp.zeros((n, *hw, 3), jnp.uint8)
    if kind == "full":
        return jnp.full((n, *hw, 3), 255, jnp.uint8)
    rng = np.random.default_rng(n)
    return jnp.asarray(rng.integers(0, 256, (n, *hw, 3)), jnp.uint8)


# The two cells' first layers at reduced H x W, and a plain first conv.
FIRST_LAYERS = {
    "alexnet_conv1_pool1": ([BConv(3, 96, kernel=11, stride=4, pad=0,
                                   first=True), Pool(3, 2)], (51, 51)),
    "yolo_conv1_pool1": ([BConv(3, 16, kernel=3, stride=1, pad=1,
                                first=True), Pool(2, 2)], (20, 20)),
    "vgg_conv1": ([BConv(3, 64, kernel=3, stride=1, pad=1, first=True)],
                  (12, 12)),
}


@pytest.fixture(scope="module")
def graphs():
    return {name: _first_layer_graph(spec, hw)
            for name, (spec, hw) in FIRST_LAYERS.items()}


@pytest.mark.parametrize("batch", [1, 8])
@pytest.mark.parametrize("frames", ["zeros", "full", "random"])
@pytest.mark.parametrize("layer", sorted(FIRST_LAYERS))
def test_bit_exact_with_bitplane_oracle(graphs, layer, frames, batch):
    g = graphs[layer]
    x = _frames(frames, batch, FIRST_LAYERS[layer][1])
    mxu = GraphExecutor(g, "mxu_pm1")
    (conv,) = mxu.backends
    assert mxu.backends[conv] == runtime.IMAGE_BACKEND
    got = np.asarray(mxu(x))
    ref = np.asarray(GraphExecutor(g, "xla")(x))
    np.testing.assert_array_equal(got, ref)
    if frames == "random":
        # both bit values occur: the thresholds were exercised
        bits = packing.unpack_bits(got, g.nodes[conv].attrs["channels"])
        assert 0 < float(np.mean(bits)) < 1


def test_filters_prepared_at_build(graphs):
    """Column-grouped ±1 filters and C1 come from the bit-plane words
    once, as operands of the executable: each of the G output columns
    holds the whole K-tap filter, shifted, and C1 = 255·(K + Σw)/2."""
    g = graphs["yolo_conv1_pool1"]
    ex = GraphExecutor(g, "vpu_direct_pool")
    (conv,) = ex.backends
    arrays = ex.arrays[str(conv)]
    w, c1 = np.asarray(arrays["w_image"], np.float32), arrays["c1"]
    group = image_conv.column_group(16, 20)
    assert arrays["w_image"].dtype == jnp.bfloat16
    assert w.shape == (3, 2, 3 * group, 16 * group)     # (KH, KWg, .., ..)
    assert set(np.unique(w)) == {-1.0, 0.0, 1.0}
    k = 3 * 3 * 3
    assert ((w != 0).sum(axis=(0, 1, 2)) == k).all()
    w_sum = w.sum(axis=(0, 1, 2)).reshape(group, 16)
    assert (w_sum == w_sum[0]).all()
    np.testing.assert_array_equal(np.asarray(c1), 255 * (k + w_sum[0]) // 2)
    assert "w_image" not in g.nodes[conv].params      # graph untouched


@pytest.mark.parametrize("out_channels,out_width,group", [
    (16, 416, 8),              # YOLOv2-Tiny conv1: 8·16 lanes, 8 | 416
    (96, 55, 5),               # AlexNet conv1: 5 | 55 beats 2 (2·96 lanes)
    (64, 224, 2),              # VGG16 conv1
    (16, 37, 8),               # no divisor in reach: the lanes decide
    (128, 7, 1), (256, 9, 1),
    (96, 0, 2),                # width unknown
])
def test_column_group_fills_mxu_lanes(out_channels, out_width, group):
    assert image_conv.column_group(out_channels, out_width) == group


@pytest.mark.parametrize("k_valid,exact", [
    (363, True),               # AlexNet conv1: 11*11*3
    (27, True),                # YOLOv2-Tiny and VGG16 conv1: 3*3*3
    (65793, True),             # 255*K = 2^24 - 1
    (65794, False),            # 255*K > 2^24
])
def test_exactness_guard(k_valid, exact):
    assert image_conv.exact(k_valid) is exact


def test_first_layer_past_the_guard_keeps_bitplane_path():
    """255*K >= 2^24: the node is no MXU candidate, every mode keeps the
    bit-plane path, and asking for the MXU lowering is an error."""
    spec = [BConv(545, 32, kernel=11, stride=4, pad=0, first=True)]
    assert not image_conv.exact(spec[0].k_valid)
    params = bnn_model.init_params(jax.random.key(0), spec)
    g = lower_packed(spec, converter.convert(params, spec, (11, 11)),
                     (11, 11))
    conv = next(nid for nid in g.nodes if g.nodes[nid].attrs.get("first"))
    assert image_conv_expand(g, conv) is None
    assert GraphExecutor(g, "mxu_pm1").backends[conv] == "mxu_pm1"
    assert runtime.Autotuner(candidates=("xla",), persist=False).tune(
        g, (1, 11, 11, 545))[conv] == "xla"
    with pytest.raises(ValueError, match="mxu_image"):
        GraphExecutor(g, {conv: runtime.IMAGE_BACKEND})


@pytest.mark.parametrize("mode", ["xla", "xla_pm1", "vpu_popcount"])
def test_oracles_keep_bitplane_path(graphs, mode):
    g = graphs["alexnet_conv1_pool1"]
    ex = GraphExecutor(g, mode)
    assert runtime.IMAGE_BACKEND not in ex.backends.values()


# --------------------------------------------------------------------------
# Whole graphs: AlexNet- and YOLO-shaped conformance nets
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def refs():
    out = {}
    for name in ("alexnet_imagenet", "yolov2_tiny_voc"):
        wl = workloads.get(name, variant="tiny", seed=7)
        h, w = wl.input_hw
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.integers(0, 256, (2, h, w, 3)), jnp.uint8)
        out[name] = (x, np.asarray(wl.engine.raw(x)))
    return out


@pytest.mark.parametrize("mode", ["vpu_chain", "vpu_direct_pool", "auto"])
@pytest.mark.parametrize("name", ["alexnet_imagenet", "yolov2_tiny_voc"])
def test_whole_graph_bit_exact_without_expansion(refs, name, mode):
    x, ref = refs[name]
    wl = workloads.get(name, variant="tiny", seed=7, matmul_mode=mode)
    np.testing.assert_array_equal(np.asarray(wl.engine.raw(x)), ref)
    exe = wl.engine.engine.compile(x.shape[0])
    g = exe.graph
    first = next(nid for nid in g.topo_order()
                 if g.nodes[nid].attrs.get("first"))
    rows = {r["node"]: r for r in exe.backend_report()}
    assert rows[first]["backend"] == runtime.IMAGE_BACKEND
    # No bit-plane expansion runs: no device op carries its scope, and
    # the image argument is charged to the first conv.
    found = {sc for mp in exe.op_scopes(x).values() for sc in mp.values()}
    assert f"n{first}.{g.nodes[first].op}" in found
    assert not any(sc.endswith(".bitplane_expand") for sc in found)
    assert exe._arg_scopes()["x"] == f"n{first}.{g.nodes[first].op}"
    for chain in exe.regions:
        assert first not in chain.node_ids
    if name == "yolov2_tiny_voc" and mode == "vpu_chain":
        # the chain that followed conv1 still forms, from conv2 on
        assert exe.regions[0].head == g.consumers()[first][0]
