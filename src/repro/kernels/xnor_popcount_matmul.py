"""Pallas TPU kernel: xor+popcount matmul on channel-packed words (Eqn 1).

Computes cnt[m, n] = sum_w ww[w] * popcount(a[m, w] ^ b[n, w]) for packed
int32 operands.  This is the paper's binary-convolution inner loop (C1/C3):
the reduction dim W is the packed channel dim — minor-most in memory, so an
HBM->VMEM block copy streams contiguous words (C7, coalesced access), and
the xor/popcount runs on the VPU's 8x128 int32 lanes.

Tiling: grid (M/bm, N/bn, W/bk).  The (bm, bn) int32 accumulator lives in a
VMEM scratch buffer across the sequential k steps (the TPU grid's innermost
dim), which is the Pallas analogue of the paper's private-memory per-thread
accumulation (C6); Pallas double-buffers the a/b block DMAs against compute
(C7, latency hiding).

The inner reduction is an *outer-product walk* over the packed words
(DESIGN.md §5.2): ``b`` arrives transposed, (W, N), so word w of every
filter is one sublane row; word w of every patch is one lane column of
``a``.  Each step xors the lane-broadcast column against the
sublane-broadcast row — a whole (bm, bn) VPU op with output channels on
the lanes — then popcounts and accumulates.  Every operand stays 2-D, which
is what the TPU's Mosaic compiler lowers.  The per-word ``fori_loop`` form
is kept selectable (``reduction="loop"``) purely so benchmarks/kernels_bench
can measure the unrolled walk against it; it is not a serving path.

The optional per-word weight row ``ww`` (1, W) implements Eqn 2's
bit-plane powers 2^(n-1) so the first layer reuses this same kernel.
Block shapes follow the TPU (8, 128) rule: the defaults ``min(128, dim)``
are either 128 or the whole dim, so they are legal for every shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

REDUCTIONS = ("vector", "loop")

# Scoped-VMEM ceiling handed to Mosaic for every kernel of the packed path.
# A v5e TensorCore has 128 MiB of VMEM; Mosaic's default scope is 16 MiB,
# which the halo-tiled conv kernels outgrow at 416x416 inputs.
VMEM_LIMIT_BYTES = 96 * 2 ** 20


def tile_counts(a: jnp.ndarray, bt: jnp.ndarray,
                ww: jnp.ndarray) -> jnp.ndarray:
    """Weighted xor-popcount of a (m, k) word tile against a transposed
    (k, n) filter tile with a (1, k) weight row -> (m, n) int32.

    Statically unrolled over the k words: each step is one whole-tile
    xor / population_count / multiply-add on (m, n), with the patch word
    broadcast along lanes and the filter word along sublanes."""
    acc = None
    for w in range(a.shape[1]):
        c = jax.lax.population_count(
            jax.lax.bitwise_xor(a[:, w:w + 1], bt[w:w + 1, :])) \
            * ww[:, w:w + 1]
        acc = c if acc is None else acc + c
    return acc


def tile_counts_loop(a: jnp.ndarray, bt: jnp.ndarray,
                     ww: jnp.ndarray) -> jnp.ndarray:
    """Per-word ``fori_loop`` reduction (benchmark baseline only): one
    packed word per step, the word's column picked out of ``a`` by a
    masked lane reduction (and its filter row by a masked sublane
    reduction) — scalar-ish on the VPU."""
    lane_a = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    lane_w = jax.lax.broadcasted_iota(jnp.int32, ww.shape, 1)
    row_b = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 0)

    def body(w, acc):
        aw = jnp.sum(jnp.where(lane_a == w, a, 0), axis=1, keepdims=True,
                     dtype=jnp.int32)                          # (m, 1)
        www = jnp.sum(jnp.where(lane_w == w, ww, 0), axis=1, keepdims=True,
                      dtype=jnp.int32)                         # (1, 1)
        bw = jnp.sum(jnp.where(row_b == w, bt, 0), axis=0, keepdims=True,
                     dtype=jnp.int32)                          # (1, n)
        return acc + jax.lax.population_count(
            jax.lax.bitwise_xor(aw, bw)) * www

    init = jnp.zeros((a.shape[0], bt.shape[1]), jnp.int32)
    return jax.lax.fori_loop(0, a.shape[1], body, init)


def _tile_counts(a, b, ww, reduction: str):
    if reduction == "vector":
        return tile_counts(a, b, ww)
    if reduction == "loop":
        return tile_counts_loop(a, b, ww)
    raise ValueError(f"unknown reduction {reduction!r}; want {REDUCTIONS}")


def _kernel(a_ref, bt_ref, ww_ref, o_ref, acc_ref, *, n_k_steps: int,
            reduction: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _tile_counts(a_ref[...], bt_ref[...], ww_ref[...],
                                 reduction)

    @pl.when(k == n_k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def compiler_params(semantics=("parallel", "parallel", "arbitrary")
                    ) -> pltpu.CompilerParams:
    """TPU dimension semantics plus the packed path's VMEM ceiling
    (ignored in interpret mode)."""
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "reduction",
                     "interpret"))
def xnor_popcount_matmul(a: jnp.ndarray, b: jnp.ndarray,
                         word_weights: jnp.ndarray | None = None,
                         *, block_m: int = 128, block_n: int = 128,
                         block_k: int = 128, reduction: str = "vector",
                         interpret: bool = False) -> jnp.ndarray:
    """a: (M, W) int32, b: (N, W) int32 -> counts (M, N) int32."""
    m, w = a.shape
    n, wb = b.shape
    assert w == wb, (a.shape, b.shape)
    if word_weights is None:
        word_weights = jnp.ones((w,), jnp.int32)

    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, w)
    gm, gn, gk = pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(w, bk)
    # Pad to block multiples; pad words are 0 in both operands and weight 0,
    # so they contribute nothing.
    a = jnp.pad(a, ((0, gm * bm - m), (0, gk * bk - w)))
    b = jnp.pad(b, ((0, gn * bn - n), (0, gk * bk - w)))
    word_weights = jnp.pad(word_weights.astype(jnp.int32),
                           (0, gk * bk - w)).reshape(1, gk * bk)

    out = pl.pallas_call(
        functools.partial(_kernel, n_k_steps=gk, reduction=reduction),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bk), lambda i, j, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((gm * bm, gn * bn), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        name="xnor_matmul",
        compiler_params=compiler_params(),
    )(a, b.T, word_weights)
    return out[:m, :n]
