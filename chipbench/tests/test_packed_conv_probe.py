"""The packed-conv probe's map from scopes to configuration layers, checked
against the regions the program's partitioner forms, and its readings on
known per-scope seconds."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import costs
from chipbench import packed_conv_probe as probe
from chipbench.harness import HERE, ROOT


def _costs(name):
    c = json.loads((HERE / "configs" / f"{name}.json").read_text())
    return c, costs.layer_costs(c["layers"], c["input_hw"])


def _program_regions(net: str, batch: int):
    """The program's graph of a paper net's conv stack, lowered from
    parameter shapes alone, and the regions its partitioner forms."""
    import itertools

    import jax

    from repro import runtime
    from repro.core import bnn_model, converter
    from repro.core.bnn_model import BConv, Pool
    from repro.models import paper_nets

    spec, (h, w, _) = paper_nets.get(net)
    spec = list(itertools.takewhile(lambda l: isinstance(l, (BConv, Pool)),
                                    spec))
    params = jax.eval_shape(lambda k: bnn_model.init_params(k, spec),
                            jax.random.key(0))
    packed = jax.eval_shape(lambda p: converter.convert(p, spec, (h, w)),
                            params)
    g = runtime.fuse_pool_epilogue(runtime.lower_packed(spec, packed, (h, w)))
    return g, runtime.partition_chains(g, (batch, h, w, 3))


# the graph ops that compute a node's layers
_NODE_OPS = {("bconv",): "packed_conv", ("bconv", "pool"): "packed_conv_pool",
             ("pool",): "or_pool"}


@pytest.mark.parametrize("name,net,batch,held", [
    ("vgg16_224", "vgg16", 8, [list(range(1, 8)), list(range(8, 18))]),
    ("yolov2_tiny_416", "yolov2-tiny", 1,
     [list(range(2, 8)), list(range(8, 14))]),
])
def test_region_layers_follow_the_programs_regions(name, net, batch, held):
    """Each region the program forms at the cell's bucket holds the layers
    its nodes compute (every node's op agrees with its layers' types), and
    every binary conv inside a region is held by exactly one region."""
    c, lcs = _costs(name)
    g, chains = _program_regions(net, batch)
    got = [probe.layers_of_nodes(ch.node_ids, lcs) for ch in chains]
    assert got == held
    nodes = probe.node_layers(lcs)
    for ch in chains:
        for nid in ch.node_ids:
            types = tuple(c["layers"][i]["type"] for i in nodes[nid])
            assert _NODE_OPS[types] == g.nodes[nid].op
    convs = [i for ls in got for i in ls if c["layers"][i]["type"] == "bconv"]
    assert len(convs) == len(set(convs))
    assert 0 not in convs                 # the first conv runs on the MXU


def test_vgg16_regions_hold_the_conv_work():
    _, lcs = _costs("vgg16_224")
    conv = sum(lc.macs for lc in lcs if probe.layer_type(lc) == "bconv")
    first, second = (sum(lcs[i].macs for i in probe.layers_of_nodes(r, lcs))
                     for r in ((4, 5, 7, 8, 9), (11, 12, 13, 15, 16, 17, 19)))
    assert (first / 1e9, second / 1e9) == (pytest.approx(7.40, abs=0.01),
                                           pytest.approx(7.86, abs=0.01))
    assert (first + second) / conv == pytest.approx(0.994, abs=0.001)


def test_region_layers_refuse_other_nodes():
    _, lcs = _costs("vgg16_224")
    assert probe.layers_of_nodes((20, 21), lcs) is None     # fc6, fc7
    assert probe.layers_of_nodes((1, 2), lcs) is None       # the expansion
    assert probe.scope_nodes("head") is None
    assert probe.scope_nodes("region.4+5") == (4, 5)


# the VGG16 cell's packed convs (conv1_2 through conv5_3) by node scope, as
# vpu_direct_pool serves them, and by region scope, as vpu_chain does
VGG_NODES = ("n4.packed_conv_pool", "n5.packed_conv", "n7.packed_conv_pool",
             "n8.packed_conv", "n9.packed_conv", "n11.packed_conv_pool",
             "n12.packed_conv", "n13.packed_conv", "n15.packed_conv_pool",
             "n16.packed_conv", "n17.packed_conv", "n19.packed_conv_pool")
VGG_REGIONS = ("region.4+5+7+8+9", "region.11+12+13+15+16+17+19")
# scopes that run no packed conv: the MXU first conv, dense, head
VGG_OTHER = {"n2.packed_conv": 0.005, "n20.packed_dense": 0.002,
             "n23.float_dense": 1e-4, "head": 0.001, "none": 1e-4}
V5E = costs.peaks("TPU v5 lite")


@pytest.mark.parametrize("conv_scopes", [VGG_NODES, VGG_REGIONS])
def test_packed_conv_reads_known_values(conv_scopes):
    _, lcs = _costs("vgg16_224")
    per_scope = dict(VGG_OTHER, **{s: 0.030 / len(conv_scopes)
                                   for s in conv_scopes})
    got = probe.packed_conv(per_scope, lcs, V5E, served=16, calls=2)
    assert set(got["scopes"]) == set(conv_scopes)
    assert got["ms_per_image"] == pytest.approx(0.030 / 16 * 1e3)
    # conv1_2 through pool5 (layers 1-17), 16 images in 2 calls
    least = costs.least_seconds(lcs[1:18], V5E, images=16, calls=2)
    assert got["roofline_pct"] == pytest.approx(100 * least / 0.030)
    # every one of those layers is compute-bound at 393 T int8 ops/s
    ops = sum(lc.ops for lc in lcs[1:18])
    assert least == pytest.approx(16 * ops / 393e12, rel=0.02)
    assert got["share_pct"] == pytest.approx(
        100 * 0.030 / (0.030 + sum(VGG_OTHER.values())))


@pytest.mark.parametrize("per_scope,served", [(VGG_OTHER, 16),
                                              ({"region.4+5": 0.01}, 0)])
def test_packed_conv_finds_nothing(per_scope, served):
    """No scope that runs a packed conv, or no image served."""
    _, lcs = _costs("vgg16_224")
    assert probe.packed_conv(per_scope, lcs, V5E, served, 1) is None


def test_probe_refuses_without_tpu():
    proc = subprocess.run(
        [sys.executable, "chipbench/packed_conv_probe.py", "--workload",
         "vgg16_224_offline", "--seed", str(2**31 + 7)],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 3
    assert "needs a TPU" in proc.stderr
    assert not proc.stdout.strip()
