"""Plain reference of a configuration: the float network it describes.

Built from the configuration file and the seed alone: nothing here imports
the program or reads what it made.  The weights are drawn from the seed in
the order and with the distributions the program documents for its seeded
checkpoint (one numpy generator, layer by layer: binary layers draw
``w, gamma, beta, mu, var`` uniform in (-1, 1), (-1.5, 1.5), (-1, 1),
(-20, 20), (0.5, 4); float layers draw ``w`` normal over sqrt(fan-in) with a
zero bias; pools draw nothing).

The forward pass is the binarized network in floating point: sign of the
latent weights, BN then sign after every binary layer, -1 padding for the
hidden binary convs and for padded pools, 0 padding for the first conv
(which sees raw uint8 pixels), and a float head.  ``dtype=float32`` runs
every contraction at ``Precision.HIGHEST``; ``dtype=bfloat16`` is the
control: the same network one precision below what the configuration
states.

Float32 ties.  A binary layer's pre-activation is an integer sum (of +-1
products, or of +-pixel values in the first layer) whose BN sign flips at
the real crossing ``xi = mu - beta * sigma / gamma``.  Where an attainable
sum lies within ``TIE_SHARE`` of the sum's full range (K, or 255 K for the
first layer) of ``xi``, float32, the precision the configuration states,
does not decide that sign: an integer threshold folded from the float32
BN parameters, as a binary engine serves them, lands on either side.  The
float32 reference decides each such channel's sign at that sum exactly,
reports which inputs hit it, and can be run with any set of ties flipped
(one decision per channel, for every input alike).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_DN = ("NHWC", "HWIO", "NHWC")
# a float32 tie: an attainable sum within this share of the sum's full
# range of the BN crossing (folds from float32 parameters were measured to
# cross at most 0.84 * 2**-24 of the range from it)
TIE_SHARE = 2.0 ** -21
_BINARY = ("bconv", "bdense")


def draw_params(layers: list[dict], seed: int) -> list[dict]:
    rng = np.random.default_rng(seed)
    params: list[dict] = []
    for layer in layers:
        t = layer["type"]
        if t in ("bconv", "bdense"):
            shape = ((layer["kernel"], layer["kernel"], layer["c_in"],
                      layer["c_out"]) if t == "bconv"
                     else (layer["d_in"], layer["d_out"]))
            o = shape[-1]
            params.append(dict(
                w=rng.uniform(-1.0, 1.0, shape).astype(np.float32),
                gamma=rng.uniform(-1.5, 1.5, o).astype(np.float32),
                beta=rng.uniform(-1, 1, o).astype(np.float32),
                mu=rng.uniform(-20, 20, o).astype(np.float32),
                var=rng.uniform(0.5, 4, o).astype(np.float32)))
        elif t in ("fconv", "fdense"):
            shape = ((layer["kernel"], layer["kernel"], layer["c_in"],
                      layer["c_out"]) if t == "fconv"
                     else (layer["d_in"], layer["d_out"]))
            fan = int(np.prod(shape[:-1]))
            params.append(dict(
                w=(rng.standard_normal(shape) / np.sqrt(fan)).astype(
                    np.float32),
                b=np.zeros(shape[-1], np.float32)))
        else:
            params.append({})
    return params


def find_ties(params: list[dict], layers: list[dict], bn_eps: float
              ) -> list[tuple[int, int, float, float]]:
    """``(layer, channel, sum, exact sign at that sum)`` of every float32
    tie, from the drawn parameters in float64."""
    ties = []
    for li, (layer, p) in enumerate(zip(layers, params)):
        if layer["type"] not in _BINARY:
            continue
        k = (layer["kernel"] ** 2 * layer["c_in"]
             if layer["type"] == "bconv" else layer["d_in"])
        g, b, mu, var = (np.asarray(p[n], np.float64)
                         for n in ("gamma", "beta", "mu", "var"))
        xi = mu - b * np.sqrt(var + bn_eps) / g
        if layer.get("first"):     # pixel sums: every integer
            full, n = 255 * k, np.round(xi)
        else:                      # sums of k signs share k's parity
            full, n = k, k - 2 * np.round((k - xi) / 2)
        near = (np.abs(n - xi) <= TIE_SHARE * full) & (np.abs(n) <= full)
        for c in np.flatnonzero(near):
            bit = 1.0 if g[c] * (n[c] - xi[c]) >= 0 else -1.0
            ties.append((li, int(c), float(n[c]), bit))
    return ties


def _sign(v):
    return jnp.where(v >= 0, 1, -1).astype(v.dtype)


def forward(params: list[dict], layers: list[dict], x_uint8, *,
            dtype=jnp.float32, bn_eps: float = 1e-4, flips=None
            ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(N, H, W, 3) uint8 -> the network's raw output (logits or map),
    computed in ``dtype`` and returned as float32, and (N, channels of
    every binary layer) whether the input's sum met that channel's
    ``tie_at``.  Binary layers whose parameters carry ``tie_at`` and
    ``tie_bit`` decide their ties by them, against ``tie_bit`` where
    ``flips`` (one entry per channel of every such layer) is 1."""
    prec = (lax.Precision.HIGHEST if dtype == jnp.float32
            else lax.Precision.DEFAULT)

    def cast(a):
        return jnp.asarray(a, dtype)

    def bn(x, p):
        sigma = jnp.sqrt(cast(p["var"]) + cast(bn_eps))
        return cast(p["gamma"]) * (x - cast(p["mu"])) / sigma + cast(p["beta"])

    def binarize(v, p):
        bits = _sign(bn(v, p))
        if "tie_at" not in p:
            return bits
        at = v == p["tie_at"]
        col = sum(h.shape[1] for h in hits)
        hits.append(jnp.any(at, axis=tuple(range(1, v.ndim - 1))))
        flip = flips[col:col + at.shape[-1]]
        decided = jnp.where(flip > 0, -p["tie_bit"], p["tie_bit"])
        return jnp.where(at, decided.astype(v.dtype), bits)

    hits: list = []
    x = cast(x_uint8)
    for layer, p in zip(layers, params):
        t = layer["type"]
        if t == "bconv":
            s, pad = layer["stride"], layer["pad"]
            if not layer.get("first") and pad:
                x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                            constant_values=-1)
                pad = 0
            x = lax.conv_general_dilated(
                x, _sign(cast(p["w"])), (s, s), [(pad, pad)] * 2,
                dimension_numbers=_DN, precision=prec)
            x = binarize(x, p)
        elif t == "pool":
            lo, hi = layer.get("pad", [0, 0])
            if lo or hi:
                x = jnp.pad(x, ((0, 0), (lo, hi), (lo, hi), (0, 0)),
                            constant_values=-1)
            k, s = layer["window"], layer["stride"]
            x = lax.reduce_window(x, cast(-jnp.inf), lax.max, (1, k, k, 1),
                                  (1, s, s, 1), "VALID")
        elif t == "bdense":
            x = jnp.matmul(x.reshape(x.shape[0], -1), _sign(cast(p["w"])),
                           precision=prec)
            x = binarize(x, p)
        elif t == "fdense":
            x = jnp.matmul(x.reshape(x.shape[0], -1), cast(p["w"]),
                           precision=prec) + cast(p["b"])
        elif t == "fconv":
            s, pad = layer["stride"], layer["pad"]
            x = lax.conv_general_dilated(
                x, cast(p["w"]), (s, s), [(pad, pad)] * 2,
                dimension_numbers=_DN, precision=prec) + cast(p["b"])
        else:
            raise ValueError(f"unknown layer type {t!r}")
    hit = (jnp.concatenate(hits, axis=1) if hits
           else jnp.zeros((x.shape[0], 0), bool))
    return x.astype(jnp.float32), hit


def letterbox(img, out_hw, fill: int, dtype=jnp.float32) -> jnp.ndarray:
    """Aspect-preserving bilinear resize of an (H, W, C) uint8 image,
    centred on a ``fill``-grey canvas of ``out_hw``, rounded to uint8."""
    h, w, c = img.shape
    oh, ow = out_hw
    scale = min(oh / h, ow / w)
    nh, nw = min(int(round(h * scale)), oh), min(int(round(w * scale)), ow)
    top, left = (oh - nh) // 2, (ow - nw) // 2
    resized = jax.image.resize(jnp.asarray(img, dtype), (nh, nw, c),
                               method="bilinear")
    canvas = jnp.full((oh, ow, c), fill, dtype)
    canvas = canvas.at[top:top + nh, left:left + nw].set(resized)
    return jnp.clip(jnp.round(canvas), 0, 255).astype(jnp.uint8)


class Reference:
    """The reference (or, with ``dtype=bfloat16``, the control) of one
    configuration at one seed: raw outputs for network-size inputs, in
    blocks of ``BLOCK`` rows so that it fits beside nothing else.  The
    float32 reference also knows its ties (``ties``, see the module
    docstring); the control has none."""

    BLOCK = 8

    def __init__(self, config: dict, seed: int, dtype=jnp.float32):
        self.config = config
        self.layers = config["layers"]
        self.dtype = dtype
        params = draw_params(self.layers, seed)
        self.ties = (find_ties(params, self.layers, config["bn_eps"])
                     if dtype == jnp.float32 else [])
        offsets, col = {}, 0
        for li, (layer, p) in enumerate(zip(self.layers, params)):
            if dtype == jnp.float32 and layer["type"] in _BINARY:
                o = len(p["gamma"])
                offsets[li], col = col, col + o
                p["tie_at"] = np.full(o, np.nan, np.float32)
                p["tie_bit"] = np.zeros(o, np.float32)
        for li, c, n, bit in self.ties:
            params[li]["tie_at"][c], params[li]["tie_bit"][c] = n, bit
        # hit column of each tie
        self._cols = np.array([offsets[li] + c for li, c, _, _ in self.ties],
                              np.int64)
        self._channels = col
        self.params = jax.device_put(params)
        self._fwd = jax.jit(lambda p, f, x: forward(
            p, self.layers, x, dtype=dtype, bn_eps=config["bn_eps"],
            flips=f))
        self._lb = jax.jit(
            lambda img: letterbox(img, tuple(config["input_hw"]),
                                  config["letterbox_fill"], dtype))

    def preprocess(self, img: np.ndarray) -> np.ndarray:
        return np.asarray(self._lb(jnp.asarray(img)))

    def run(self, x_uint8: np.ndarray, flips=()
            ) -> tuple[np.ndarray, np.ndarray]:
        """(N, H, W, 3) network-size uint8 -> float32 raw outputs, and
        (N, ties) whether each input hits each tie, with the ties whose
        indices are in ``flips`` decided against their exact sign."""
        f = np.zeros(self._channels, np.float32)
        f[self._cols[list(flips)]] = 1.0
        out, hit = [], []
        for i in range(0, len(x_uint8), self.BLOCK):
            xb = np.asarray(x_uint8[i:i + self.BLOCK])
            n = len(xb)
            if n < self.BLOCK:      # one compiled shape for every block
                xb = np.concatenate([xb, np.zeros((self.BLOCK - n,)
                                                  + xb.shape[1:], xb.dtype)])
            o, h = self._fwd(self.params, f, xb)
            out.append(np.asarray(o)[:n])
            hit.append(np.asarray(h)[:n][:, self._cols])
        return np.concatenate(out), np.concatenate(hit)
