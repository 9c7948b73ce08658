"""MAC counts of the paper nets against a hand count, and the peak table."""

import json

import pytest

from chipbench import costs
from chipbench.harness import HERE


def _config(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def _macs(name):
    c = _config(name)
    return [lc.macs for lc in costs.layer_costs(c["layers"], c["input_hw"])]


def _vgg16_layers():
    layers, c_in = [], 3
    for item in [64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
                 512, 512, 512, "M", 512, 512, 512, "M"]:
        if item == "M":
            layers.append({"type": "pool", "window": 2, "stride": 2,
                           "pad": [0, 0]})
        else:
            layers.append({"type": "bconv", "c_in": c_in, "c_out": item,
                           "kernel": 3, "stride": 1, "pad": 1})
            c_in = item
    return layers + [{"type": "bdense", "d_in": 25088, "d_out": 4096},
                     {"type": "bdense", "d_in": 4096, "d_out": 4096},
                     {"type": "fdense", "d_in": 4096, "d_out": 1000}]


def test_yolov2_tiny_416_macs():
    macs = _macs("yolov2_tiny_416")
    # hand count from the spec: 416x416x3x9x16, then 208x208x16x9x32, ...
    hand = [416 * 416 * 27 * 16, 0, 208 * 208 * 144 * 32, 0,
            104 * 104 * 288 * 64, 0, 52 * 52 * 576 * 128, 0,
            26 * 26 * 1152 * 256, 0, 13 * 13 * 2304 * 512, 0,
            13 * 13 * 4608 * 1024, 13 * 13 * 9216 * 1024,
            13 * 13 * 1024 * 125]
    assert macs == hand
    assert sum(macs) == pytest.approx(3.48e9, rel=0.01)
    assert (macs[12] + macs[13]) / sum(macs) == pytest.approx(0.69, abs=0.01)


def test_alexnet_227_macs():
    macs = _macs("alexnet_227")
    hand = [55 * 55 * 363 * 96, 0, 27 * 27 * 2400 * 256, 0,
            13 * 13 * 2304 * 384, 13 * 13 * 3456 * 384,
            13 * 13 * 3456 * 256, 0, 9216 * 4096, 4096 * 4096,
            4096 * 1000]
    assert macs == hand
    assert sum(macs) == pytest.approx(1.14e9, rel=0.01)
    assert macs[2] / sum(macs) == pytest.approx(0.40, abs=0.01)


def test_vgg16_224_macs():
    lcs = costs.layer_costs(_vgg16_layers(), (224, 224))
    assert sum(lc.macs for lc in lcs) == pytest.approx(15.5e9, rel=0.01)


def test_ops_are_two_per_mac_and_bytes_are_minimal():
    c = _config("alexnet_227")
    lcs = costs.layer_costs(c["layers"], c["input_hw"])
    for lc in lcs:
        if lc.macs:
            assert lc.ops == 2 * lc.macs
    conv1 = lcs[0]
    # uint8 pixels in, one bit per output channel out
    assert conv1.act_bytes == 227 * 227 * 3 + 55 * 55 * 96 // 8
    assert conv1.weight_bytes == 11 * 11 * 3 * 96 // 8 + 4 * 96


def test_least_time_takes_weights_once_per_call():
    c = _config("alexnet_227")
    lcs = costs.layer_costs(c["layers"], c["input_hw"])
    peak = costs.peaks("TPU v5 lite")
    one_call = costs.least_seconds(lcs, peak, images=8, calls=1)
    eight_calls = costs.least_seconds(lcs, peak, images=8, calls=8)
    assert one_call < eight_calls == pytest.approx(
        8 * costs.least_seconds(lcs, peak))


def test_peaks_v5e():
    p = costs.peaks("TPU v5 lite")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_per_s"]) == (
        197e12, 393e12, 819e9)
    assert "TPU v5e" in p["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        costs.peaks("cpu")


def test_shape_mismatch_is_an_error():
    layers = [{"type": "bconv", "c_in": 4, "c_out": 8, "kernel": 3,
               "stride": 1, "pad": 1}]
    with pytest.raises(ValueError, match="c_in"):
        costs.layer_costs(layers, (8, 8))
