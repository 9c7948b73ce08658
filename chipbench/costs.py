"""Operations and minimal bytes of each layer of a configuration, from shapes.

One multiply-accumulate counts as 2 operations.  Binary layers (the
bit-plane first conv, packed convs and packed dense layers) are charged at
the chip's int8 peak: a ±1 or uint8×±1 MAC is int8 work, and no popcount
formulation on the vector unit can beat the int8 matrix peak.  Float layers
(the head) are charged at the bf16 peak.  A max pool counts one comparison
per window element of every output bit, also at the int8 peak.

Minimal bytes are what a layer must read from and write to HBM at least
once: its input and output activations per image (packed layers: one bit
per channel; the first layer reads uint8 pixels; the float head writes
float32) and its weights once per executable call, however many images the
call serves (one bit each for binary layers plus an int32 threshold per
output channel; float32 for the head).  The least time of a layer is the
larger of its operations over the peak and its bytes over the HBM
bandwidth.

Layers are given as the dicts of a configuration file's ``layers`` list:
``{"type": "bconv"|"pool"|"bdense"|"fconv"|"fdense", ...}``.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


@dataclasses.dataclass(frozen=True)
class LayerCost:
    name: str
    kind: str            # "binary" | "float"
    macs: int            # per image (pool: 0)
    ops: int             # per image: 2 * macs, or comparisons for a pool
    act_bytes: int       # minimal activation bytes per image (in + out)
    weight_bytes: int    # weights and thresholds, read once per call


def peaks(device_kind: str) -> dict:
    """The peak table row of a device kind; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def _conv_out(n: int, k: int, s: int, pad_lo: int, pad_hi: int) -> int:
    return (n + pad_lo + pad_hi - k) // s + 1


def layer_costs(layers: list[dict], input_hw) -> list[LayerCost]:
    """Per-image cost of every layer, walking the shapes from ``input_hw``
    (an RGB uint8 image)."""
    h, w = input_hw
    c = 3
    packed_in = False        # the first layer reads uint8 pixels
    out: list[LayerCost] = []
    for i, layer in enumerate(layers):
        t = layer["type"]
        name = f"{i}:{t}"
        if t in ("bconv", "fconv"):
            k, s, p = layer["kernel"], layer["stride"], layer["pad"]
            if layer["c_in"] != c:
                raise ValueError(f"{name}: c_in {layer['c_in']} != {c}")
            ho, wo = _conv_out(h, k, s, p, p), _conv_out(w, k, s, p, p)
            co = layer["c_out"]
            macs = ho * wo * co * k * k * c
            in_bytes = h * w * (c / 8 if packed_in else c)
            if t == "bconv":
                wbytes = k * k * c * co / 8 + 4 * co
                obytes = ho * wo * co / 8
                kind = "binary"
            else:
                wbytes = 4 * (k * k * c * co + co)
                obytes = 4 * ho * wo * co
                kind = "float"
            out.append(LayerCost(name, kind, macs, 2 * macs,
                                 math.ceil(in_bytes + obytes),
                                 math.ceil(wbytes)))
            h, w, c, packed_in = ho, wo, co, True
        elif t == "pool":
            k, s = layer["window"], layer["stride"]
            lo, hi = layer.get("pad", [0, 0])
            ho, wo = _conv_out(h, k, s, lo, hi), _conv_out(w, k, s, lo, hi)
            ops = ho * wo * c * k * k
            nbytes = math.ceil((h * w + ho * wo) * c / 8)
            out.append(LayerCost(name, "binary", 0, ops, nbytes, 0))
            h, w = ho, wo
        elif t in ("bdense", "fdense"):
            d_in, d_out = layer["d_in"], layer["d_out"]
            if d_in != h * w * c:
                raise ValueError(f"{name}: d_in {d_in} != {h * w * c}")
            macs = d_in * d_out
            if t == "bdense":
                abytes, wbytes = d_in / 8 + d_out / 8, d_in * d_out / 8 + 4 * d_out
                kind = "binary"
            else:
                abytes, wbytes = d_in / 8 + 4 * d_out, 4 * (d_in * d_out + d_out)
                kind = "float"
            out.append(LayerCost(name, kind, macs, 2 * macs,
                                 math.ceil(abytes), math.ceil(wbytes)))
            h, w, c = 1, 1, d_out
        else:
            raise ValueError(f"{name}: unknown layer type {t!r}")
    return out


def least_seconds(costs: list[LayerCost], peak: dict, images: int = 1,
                  calls: int = 1) -> float:
    """Least time on one chip for ``images`` images served in ``calls``
    executable calls: per layer the larger of its compute bound and its
    HBM bound, summed."""
    total = 0.0
    for lc in costs:
        rate = peak["int8_ops"] if lc.kind == "binary" else peak["bf16_flops"]
        nbytes = images * lc.act_bytes + calls * lc.weight_bytes
        total += max(images * lc.ops / rate,
                     nbytes / peak["hbm_bytes_per_s"])
    return total


def peak_seconds(costs: list[LayerCost], peak: dict) -> float:
    """Time for one image's MACs (2 operations each) at the compute peaks
    alone, pools left out: the per-image cost behind a model-FLOP
    utilization.  Binary MACs at the int8 peak, float MACs at bf16."""
    return sum(2 * lc.macs / (peak["int8_ops"] if lc.kind == "binary"
                              else peak["bf16_flops"]) for lc in costs)
