"""The VGG16@224 cell: its configuration is the network the program serves,
layer for layer, with a hand count of its MACs; and its harness run at the
program's tiny VGG passes the comparison, and fails it where a served
answer is altered."""

import json

import pytest

from chipbench import costs
from chipbench.harness import HERE
from chipbench.tests.test_harness_cpu import _bconv, _pool, run, tiny_cell

# the program's vgg16_imagenet variant="tiny", layer for layer
TINY_VGG = [_bconv(3, 16, first=True), _bconv(16, 16), _pool(),
            _bconv(16, 32), _bconv(32, 32), _pool(),
            dict(type="bdense", d_in=512, d_out=64),
            dict(type="bdense", d_in=64, d_out=64),
            dict(type="fdense", d_in=64, d_out=10)]


def _config(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def _as_config_layer(layer) -> dict:
    from repro.core.bnn_model import BConv, BDense, FloatConv, FloatDense

    if isinstance(layer, (BConv, FloatConv)):
        d = dict(type="bconv" if isinstance(layer, BConv) else "fconv",
                 c_in=layer.c_in, c_out=layer.c_out, kernel=layer.kernel,
                 stride=layer.stride, pad=layer.pad)
        return dict(d, first=True) if getattr(layer, "first", False) else d
    if isinstance(layer, (BDense, FloatDense)):
        return dict(type="bdense" if isinstance(layer, BDense) else "fdense",
                    d_in=layer.d_in, d_out=layer.d_out)
    return dict(type="pool", window=layer.window, stride=layer.stride,
                pad=list(layer.pad))


@pytest.mark.parametrize("name,net", [("vgg16_224", "vgg16"),
                                      ("alexnet_227", "alexnet"),
                                      ("yolov2_tiny_416", "yolov2-tiny")])
def test_config_layers_are_the_programs(name, net):
    """A configuration's layers and input size are the network the program
    serves under its workload (``models/paper_nets.py``), so the reference
    and the costs cannot drift from what runs."""
    from repro.models import paper_nets

    c = _config(name)
    spec, (h, w, _) = paper_nets.get(net)
    assert c["layers"] == [_as_config_layer(layer) for layer in spec]
    assert c["input_hw"] == [h, w]


def test_tiny_vgg_is_the_programs():
    from repro.workloads import workload

    spec, (h, w) = workload._tiny_vgg16()
    assert TINY_VGG == [_as_config_layer(layer) for layer in spec]
    assert [h, w] == tiny_vgg_cell().config["input_hw"]


def test_vgg16_224_config_macs():
    c = _config("vgg16_224")
    macs = [lc.macs for lc in costs.layer_costs(c["layers"], c["input_hw"])]
    # hand count from Table 1 config D: 224x224x27x64, 224x224x576x64, ...
    hand = [224 * 224 * 27 * 64, 224 * 224 * 576 * 64, 0,
            112 * 112 * 576 * 128, 112 * 112 * 1152 * 128, 0,
            56 * 56 * 1152 * 256] + [56 * 56 * 2304 * 256] * 2 + [0] + [
            28 * 28 * 2304 * 512] + [28 * 28 * 4608 * 512] * 2 + [0] + [
            14 * 14 * 4608 * 512] * 3 + [0, 25088 * 4096, 4096 * 4096,
                                         4096 * 1000]
    assert macs == hand
    assert sum(macs) == pytest.approx(15.47e9, rel=0.01)


def tiny_vgg_cell():
    cell = tiny_cell("vgg16_224_offline")
    cell.config = dict(cell.config, layers=TINY_VGG)
    return cell


def test_tiny_vgg_run_is_correct():
    line = run(tiny_vgg_cell())
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"images_per_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"


def _swap_ranks(out):
    # the classes of the top-1 and top-5 rows swapped, probabilities kept
    return out.at[:, 0, 0].set(out[:, -1, 0]).at[:, -1, 0].set(out[:, 0, 0])


def test_altered_vgg_answer_is_not_correct(monkeypatch):
    line = run(tiny_vgg_cell(), monkeypatch, _swap_ranks)
    assert not line["correct"], line["checks"]
