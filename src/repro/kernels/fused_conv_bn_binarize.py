"""Pallas TPU kernel: integrated binary-conv + BN + binarize + bit-pack (C4+C6).

The im2col-shaped fused PhoneBit kernel.  One output tile:

  1. accumulates xor-popcounts over the packed reduction dim (Eqn 1) with
     the whole-tile vectorized reduction of ``xnor_popcount_matmul``
     (block xor -> population_count -> weighted reduction; the legacy
     per-word ``fori_loop`` is selectable as ``reduction="loop"`` for
     benchmarking only),
  2. applies the offline-folded integer threshold  bit = (cnt <= t) xor s
     (Eqns 5-9, integer-strengthened form, branch-free on the VPU),
  3. bit-packs 32 output channels per int32 word *in-register* and performs a
     single packed store — the TPU analogue of Fig 4's "one thread computes
     8 filters, binarizes 8 results and packs into one byte".

No float op and no unpacked intermediate ever reaches VMEM/HBM, which is
exactly the paper's layer-integration claim (§V-B): intermediate results
between conv/BN/binarization layers are never materialized in memory.

Operands are im2col patches (matmul-shaped); the conv wrapper lives in
``repro.kernels.ops.fused_binary_conv2d``.  For the im2col-*free* direct
convolution form of the same contract see
``repro.kernels.direct_conv_bn_binarize`` (DESIGN.md §5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import WORD_BITS
from repro.kernels.xnor_popcount_matmul import _tile_counts, compiler_params


def pack_words(bits: jnp.ndarray) -> jnp.ndarray:
    """In-register bit-pack of the minor axis: (m, n*32) {0,1} int32 ->
    (m, n) int32 words, LSB-first.

    Done as two small matmuls against a block-diagonal power-of-two
    matrix built from iotas (Pallas kernels take no captured constants):
    one for bits 0-15, one for bits 16-31.  Every product is a 0/1 bf16
    times an exact bf16 power of two and every sum stays below 2^16, so
    the f32 accumulation is exact; the halves recombine with a shift
    (bit 31 wraps to INT32_MIN — the two's-complement pattern modular
    int32 accumulation expects).  A lane-splitting reshape would say the
    same thing, but Mosaic does not lower one.
    """
    n_bits = bits.shape[-1]
    shape = (n_bits, n_bits // WORD_BITS)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    bit = row % WORD_BITS
    own = (row // WORD_BITS) == col
    power = jax.lax.shift_left(jnp.int32(1), bit % 16).astype(jnp.float32)
    lo = jnp.where(own & (bit < 16), power, 0.0).astype(jnp.bfloat16)
    hi = jnp.where(own & (bit >= 16), power, 0.0).astype(jnp.bfloat16)
    b = bits.astype(jnp.bfloat16)
    w_lo = jnp.dot(b, lo, preferred_element_type=jnp.float32)
    w_hi = jnp.dot(b, hi, preferred_element_type=jnp.float32)
    return w_lo.astype(jnp.int32) | jax.lax.shift_left(
        w_hi.astype(jnp.int32), 16)


def threshold_pack(cnt: jnp.ndarray, t: jnp.ndarray,
                   s: jnp.ndarray) -> jnp.ndarray:
    """Fused epilogue on a count tile: integer threshold (Eqn 9's
    ``(cnt <= t) xor s`` form) + in-register 32-channel bit-pack.
    cnt: (m, n); t, s: (1, n) int32 -> (m, n//32) int32 words."""
    bits = (jnp.less_equal(cnt, t).astype(jnp.int32) ^ s)
    return pack_words(bits)


def _kernel(a_ref, bt_ref, ww_ref, t_ref, s_ref, o_ref, acc_ref,
            *, n_k_steps: int, reduction: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _tile_counts(a_ref[...], bt_ref[...], ww_ref[...],
                                 reduction)

    @pl.when(k == n_k_steps - 1)
    def _epilogue():
        o_ref[...] = threshold_pack(acc_ref[...], t_ref[...], s_ref[...])


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "reduction",
                     "interpret"))
def fused_matmul_bn_binarize(a: jnp.ndarray, b: jnp.ndarray,
                             threshold: jnp.ndarray, sign_flip: jnp.ndarray,
                             word_weights: jnp.ndarray | None = None,
                             *, block_m: int = 128,
                             block_n: int | None = None,
                             block_k: int = 128, reduction: str = "vector",
                             interpret: bool = False) -> jnp.ndarray:
    """a: (M, W) patches, b: (N, W) filters -> packed bits (M, ceil(N/32)).

    threshold: (N,) int32; sign_flip: (N,) bool.  Output channel padding
    (N -> block multiple) uses threshold=-1 / sign=0 so pad bits are 0,
    matching ``packing.pack_bits`` semantics.  The default ``block_n``
    (None) spans every filter: the packed output block is then the whole
    word dim, which the TPU (8, 128) block rule always accepts.
    """
    m, w = a.shape
    n, wb = b.shape
    assert w == wb
    if word_weights is None:
        word_weights = jnp.ones((w,), jnp.int32)

    bm, bk = min(block_m, m), min(block_k, w)
    if block_n is None:
        bn = -(-n // WORD_BITS) * WORD_BITS
    else:
        bn = min(block_n, max(WORD_BITS, n))
        bn = max(WORD_BITS, (bn // WORD_BITS) * WORD_BITS)
    gm, gn, gk = pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(w, bk)

    a = jnp.pad(a, ((0, gm * bm - m), (0, gk * bk - w)))
    b = jnp.pad(b, ((0, gn * bn - n), (0, gk * bk - w)))
    word_weights = jnp.pad(word_weights.astype(jnp.int32),
                           (0, gk * bk - w)).reshape(1, gk * bk)
    threshold = jnp.pad(threshold.astype(jnp.int32), (0, gn * bn - n),
                        constant_values=-1).reshape(1, gn * bn)
    sign_flip = jnp.pad(sign_flip.astype(jnp.int32),
                        (0, gn * bn - n)).reshape(1, gn * bn)

    nw = bn // WORD_BITS
    out = pl.pallas_call(
        functools.partial(_kernel, n_k_steps=gk, reduction=reduction),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bk), lambda i, j, k: (0, k)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, nw), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((gm * bm, gn * nw), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        compiler_params=compiler_params(),
    )(a, b.T, word_weights, threshold, sign_flip)
    return out[:m, : -(-n // WORD_BITS)]
