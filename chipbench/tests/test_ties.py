"""Float32 ties of the BN sign (``reference.find_ties``) and how the check
decides them.

A threshold folded from the float32 BN parameters crosses the real one
only on channels that ``find_ties`` names; the check accepts answers served
with such a channel decided either way, and refuses a threshold moved one
count where float32 decides the sign."""

import json
import pathlib

import numpy as np
import pytest

from chipbench import compare, harness, reference

from .test_control import small_cell
from .test_harness_cpu import SEED

CONFIGS = pathlib.Path(reference.__file__).parent / "configs"


def float32_fold_crossings(layers, params, bn_eps):
    """(layer, channel) where an integer threshold folded in float32 (the
    popcount form ``(K - xi) / 2``, or ``255 (K + sum w) / 2 - xi`` for
    the first layer) differs from the one folded in float64."""
    out = []
    for li, (layer, p) in enumerate(zip(layers, params)):
        if layer["type"] not in ("bconv", "bdense"):
            continue
        k = (layer["kernel"] ** 2 * layer["c_in"]
             if layer["type"] == "bconv" else layer["d_in"])
        g, b, mu, var = (p[n] for n in ("gamma", "beta", "mu", "var"))
        xi32 = mu - b * np.sqrt(var + np.float32(bn_eps)) / g
        xi64 = mu.astype(np.float64) - b.astype(np.float64) * np.sqrt(
            var.astype(np.float64) + bn_eps) / g.astype(np.float64)
        if layer.get("first"):
            c1 = 255 * (k + np.sum(np.where(p["w"] >= 0, 1, -1),
                                   axis=(0, 1, 2))) / 2
            a32, a64 = np.float32(c1) - xi32, c1 - xi64
        else:
            a32, a64 = (np.float32(k) - xi32) / np.float32(2), (k - xi64) / 2
        cross = (np.where(g > 0, np.floor(a32), np.ceil(a32))
                 != np.where(g > 0, np.floor(a64), np.ceil(a64)))
        out += [(li, int(c)) for c in np.flatnonzero(cross)]
    return out


@pytest.mark.parametrize("config,seed", [
    ("yolov2_tiny_416", 1646343904), ("yolov2_tiny_416", 2147483820),
    ("yolov2_tiny_416", 3000000013), ("alexnet_227", 2147483701),
    ("alexnet_227", 7000000012)])
def test_float32_fold_crossings_are_ties(config, seed):
    cfg = json.loads((CONFIGS / f"{config}.json").read_text())
    params = reference.draw_params(cfg["layers"], seed)
    crossings = float32_fold_crossings(cfg["layers"], params, cfg["bn_eps"])
    ties = {(li, c) for li, c, _, _ in
            reference.find_ties(params, cfg["layers"], cfg["bn_eps"])}
    assert crossings and set(crossings) <= ties, (crossings, ties)


# the "program" serves the reference with this tie flipped: channel 26 of
# conv2, at a share of the range where it is a tie (3e-6), but not at
# float32's
WIDE_SHARE, FLIPPED = 3e-6, (2, 26)


@pytest.mark.parametrize("share,correct", [(WIDE_SHARE, True),
                                           (reference.TIE_SHARE, False)])
def test_flipped_threshold(monkeypatch, share, correct):
    cell = small_cell("alexnet227_offline")
    inputs = harness.generator(cell.traffic["kind"]).make_inputs(
        cell.traffic, cell.config, SEED)
    monkeypatch.setattr(reference, "TIE_SHARE", WIDE_SHARE)
    ref = reference.Reference(cell.config, SEED)
    t = next(i for i, tie in enumerate(ref.ties) if tie[:2] == FLIPPED)
    used = list(range(len(inputs["payloads"])))
    raw, hits = ref.run(harness.network_inputs(ref, cell.traffic, inputs,
                                               used), {t})
    assert hits[:, t].any()
    served = [{"input": i, "result": compare.answer_rows(cell.config, raw[i])}
              for i in used]
    monkeypatch.setattr(reference, "TIE_SHARE", share)
    ok, checks = compare.verdict(
        harness.check_answers(cell.config, cell.traffic, SEED, inputs,
                              served), cell.config["limits"])
    assert ok == correct, checks
