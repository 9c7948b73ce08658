"""Pallas TPU kernel: first-layer bit-plane split + channel packing (C8).

(N, H, W, C) 8-bit input -> (N, H, W, 8*Cw) int32: 8 bit-planes (Eqn 2),
each packed along the channel dim (C2).  Pure data movement + bit twiddling;
one pass over the image, packed words written once.  The output word layout
is plane-major per pixel — plane n occupies words [n*Cw, (n+1)*Cw) — matching
``bitplanes.plane_word_weights`` and the first-layer filter packing in
``converter.convert``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bitplanes import NUM_PLANES
from repro.core.packing import WORD_BITS, num_words
from repro.kernels.xnor_popcount_matmul import compiler_params

def _kernel(x_ref, o_ref, *, channels: int):
    x = x_ref[...]                             # (1, bh, w, C) int32
    cw = num_words(channels)
    for n in range(NUM_PLANES):
        for wi in range(cw):
            lo = wi * WORD_BITS
            word = None
            for c in range(lo, min(lo + WORD_BITS, channels)):
                bit = jax.lax.shift_left(
                    (x[..., c:c + 1] >> n) & 1, jnp.int32(c - lo))
                word = bit if word is None else word | bit
            k = n * cw + wi
            o_ref[..., k:k + 1] = word         # (1, bh, w, 1) lane column


@functools.partial(jax.jit, static_argnames=("block_h", "interpret"))
def bitplane_pack(x: jnp.ndarray, *, block_h: int = 8,
                  interpret: bool = False) -> jnp.ndarray:
    """x: (N, H, W, C) uint8/int -> (N, H, W, 8*Cw) int32 packed planes.

    Blocks are ``block_h`` whole image rows.  On the TPU both the C input
    channels and the 8*Cw output words sit on the 128-lane minor axis, so
    a row costs W x 128 x 4 bytes of VMEM in and out whatever C is: the
    default of 8 rows keeps a 416-wide image's double-buffered blocks
    near 7 MiB.
    """
    n, h, w, c = x.shape
    x = x.astype(jnp.int32)  # widen on entry; kernel works on int32 lanes
    bh = min(block_h, h)
    gh = pl.cdiv(h, bh)
    pad_h = gh * bh - h
    if pad_h:
        x = jnp.pad(x, ((0, 0), (0, pad_h), (0, 0), (0, 0)))
    cw = num_words(c)
    out = pl.pallas_call(
        functools.partial(_kernel, channels=c),
        grid=(n, gh),
        in_specs=[pl.BlockSpec((1, bh, w, c), lambda i, j: (i, j, 0, 0))],
        out_specs=pl.BlockSpec((1, bh, w, NUM_PLANES * cw),
                               lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, gh * bh, w, NUM_PLANES * cw),
                                       jnp.int32),
        interpret=interpret,
        compiler_params=compiler_params(("parallel", "parallel")),
    )(x)
    return out[:, :h]
