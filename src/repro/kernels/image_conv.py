"""The first binary conv as one exact integer conv on the MXU, straight
from the uint8 image.

The bit-plane first layer (paper §III-B, Eqn 2) splits each 8-bit pixel
into 8 planes and runs one weighted xor-popcount per plane word: with 3
input channels a 32-bit word carries 3 valid bits, so the VPU walk spends
KH·KW·8 whole-tile steps per output row on a handful of bits.  The sum it
reconstructs is s = Σ x·w over uint8 pixels x and ±1 weights w, which is
an ordinary integer convolution, and the MXU computes it exactly:

* every uint8 value and every ±1 is an exact bf16 number, so each product
  is exact;
* |s| ≤ 255·K (K = KH·KW·C), and while that stays below 2^24 every
  partial sum is an integer that f32 accumulation holds exactly
  (:func:`exact`).

So one bf16 conv with f32 accumulation gives s exactly, and the same
integer epilogue as the bit-plane path follows: the weighted count
wcnt = C1 − s (C1 = 255·(K + Σw)/2, an exact integer per filter) against
the threshold and sign that ``layer_integration.fold_bn_first_layer``
folded, then the OR-pool and the 32-channel bit-pack.  Every bit is the
bit-plane path's.  Zero padding is exact too: a padded pixel is 0, which
is what a zero word of every plane stands for.

An RGB image gives the MXU a short contraction (K = KH·KW·3) and few
output lanes (16 filters at YOLOv2-Tiny's conv1), so the image is
regrouped into blocks of stride·G columns on the channel axis (a
space-to-depth) and one conv position computes G adjacent output columns
(:func:`column_group`: G·O fills the 128 lanes) against a block-sparse
filter; its zeros add exact zeros.

The filters are recovered from the bit-plane words once, when the
executable is built (:func:`pm1_weights`); the executor then feeds the
node the uint8 image and no bit-plane expansion runs.  XLA lowers the
conv and fuses the epilogue (DESIGN.md §3.6).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import bitplanes, layer_integration, packing

# f32 holds every integer of magnitude up to 2^24 exactly.
EXACT_BOUND = 1 << 24
# Output lanes of one MXU pass on the TPU.
MXU_LANES = 128


def exact(k_valid: int) -> bool:
    """Whether the largest first-layer sum, 255·K, stays exact under f32
    accumulation.  A first layer that fails this keeps the bit-plane path."""
    return 255 * k_valid < EXACT_BOUND


def column_group(out_channels: int, out_width: int) -> int:
    """How many adjacent output columns G one conv position computes:
    enough that their G·O filters fill the MXU's output lanes (16 filters
    at YOLOv2-Tiny's conv1 use an eighth of them alone), preferring the
    smallest such G that divides the output width, which leaves no padded
    column to compute and slice away (on a v5e: AlexNet's conv1 at G = 5
    of 55 columns beat G = 2, 3, 4 and 8; PERF.md §6)."""
    need = -(-MXU_LANES // out_channels)
    return next((g for g in range(need, 4 * need + 1) if out_width % g == 0),
                need)


def pm1_weights(w_packed, kernel: int, stride: int, c_in: int, group: int
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Bit-plane filter words -> (column-grouped ±1 bf16 filters, C1 per
    filter).

    ``w_packed`` is the first layer's (O, KH·KW·8·Cw) int32 filter in
    (kh, kw, plane, word) order (``converter.convert``): every plane holds
    the same sign bits, so plane 0's words are the filter.  The grouped
    filter computes ``group`` adjacent output columns at one position of
    the image regrouped into blocks of ``stride·group`` columns
    (:func:`image_conv_bn_binarize`): shape (KH, KWg, stride·group·C,
    group·O), holding filter column kw = dg·stride·group + gi − stride·go
    for output column go at block offset dg and input column gi, and zero
    where that falls outside the kernel.  Runs once on the host, at build
    time.
    """
    w = np.asarray(w_packed)
    o, cw = w.shape[0], packing.num_words(c_in)
    words = w.reshape(o, kernel, kernel, bitplanes.NUM_PLANES, cw)[..., 0, :]
    shifts = np.arange(packing.WORD_BITS, dtype=np.uint32)
    bits = (words.view(np.uint32)[..., None] >> shifts) & 1
    bits = bits.reshape(o, kernel, kernel, cw * packing.WORD_BITS)[..., :c_in]
    pm1 = 2 * bits.astype(np.int32) - 1                     # (O, KH, KW, C)
    k_valid = kernel * kernel * c_in
    # K + Σw is even (Σw ≡ K mod 2), so C1 is an exact integer.
    c1 = 255 * (k_valid + pm1.sum(axis=(1, 2, 3))) // 2
    hwio = pm1.transpose(1, 2, 3, 0)                        # (KH, KW, C, O)
    span = stride * group
    blocks = -(-(stride * (group - 1) + kernel) // span)
    grouped = np.zeros((kernel, blocks, span * c_in, group * o), np.float32)
    for dg in range(blocks):
        for gi in range(span):
            for go in range(group):
                kw = dg * span + gi - stride * go
                if 0 <= kw < kernel:
                    grouped[:, dg, gi * c_in:(gi + 1) * c_in,
                            go * o:(go + 1) * o] = hwio[:, kw]
    return (jnp.asarray(grouped, jnp.bfloat16), jnp.asarray(c1, jnp.int32))


def image_conv_bn_binarize(
        x: jnp.ndarray, w: jnp.ndarray, c1: jnp.ndarray,
        p: layer_integration.IntegratedParams, *, kernel: int, stride: int,
        pad: int, pool: tuple[int, int, tuple[int, int]] | None = None
        ) -> jnp.ndarray:
    """First binary conv(+OR-pool) on the MXU: (N, H, W, C) uint8 in,
    (N, OH', OW', ceil(O/32)) packed int32 words out, bit-exact with the
    bit-plane path.  ``w`` is :func:`pm1_weights`'s grouped filter;
    ``pool`` an optional ``(window, stride, pad)``.

    The image is zero-padded (pixel 0) and regrouped into blocks of
    stride·G columns on the channel axis, so one conv position yields G
    adjacent output columns; the OR-pool runs on the bits before they are
    packed."""
    n, h, width, c = x.shape
    span = w.shape[2] // c
    group = span // stride
    o = w.shape[3] // group
    ow = (width + 2 * pad - kernel) // stride + 1
    n_groups = -(-ow // group)
    cols = span * (n_groups + w.shape[1] - 1)
    x = jnp.pad(x, ((0, 0), (0, 0), (pad, max(0, cols - width - pad)),
                    (0, 0)))[:, :, :cols]
    x = x.reshape(n, h, cols // span, span * c)
    s = lax.conv_general_dilated(
        x.astype(jnp.bfloat16), w, (stride, 1), [(pad, pad), (0, 0)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)
    s = s.reshape(n, s.shape[1], n_groups * group, o)[:, :, :ow]
    bits = layer_integration.apply_threshold(
        c1 - s.astype(jnp.int32), p).astype(jnp.bool_)
    if pool is not None:
        window, step, (lo, hi) = pool
        bits = lax.reduce_window(bits, False, lax.bitwise_or,
                                 (1, window, window, 1), (1, step, step, 1),
                                 ((0, 0), (lo, hi), (lo, hi), (0, 0)))
    return packing.pack_bits(bits, axis=-1)
