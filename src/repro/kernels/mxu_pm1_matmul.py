"""Pallas TPU kernel: packed-binary matmul on the MXU (beyond-paper path).

The paper's algorithm (xor+popcount) is a VPU workload.  On TPU the MXU's
bf16 throughput is ~50x the VPU's int32 op rate, so past a crossover in the
reduction dim it is faster to *unpack* packed words to +-1 bf16 inside VMEM
(32x expansion happens HBM->VMEM once per tile, never touching HBM) and feed
the systolic array:  dot_pm1(A, B) == K - 2*cnt  directly.

This keeps PhoneBit's storage/bandwidth win (HBM traffic stays packed, 32x
compressed — the paper's C2 layout) while swapping the compute engine for
the one TPUs are built around.  Whether it beats the paper-faithful VPU
kernel on a given layer is a per-layer chip measurement (the autotuner
decides per node).

The default ``block_k=128`` keeps the word block legal under the TPU
(8, 128) block rule: ``min(128, W)`` is either 128 or the whole word dim.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import WORD_BITS
from repro.kernels.xnor_popcount_matmul import compiler_params


def _unpack_pm1(words: jnp.ndarray) -> jnp.ndarray:
    """(r, wk) int32 -> (r, wk*32) bf16 in {-1, +1} (LSB-first)."""
    shifts = jnp.arange(WORD_BITS, dtype=jnp.int32)
    bits = (words[:, :, None] >> shifts[None, None, :]) & 1
    pm1 = (2 * bits - 1).astype(jnp.bfloat16)
    return pm1.reshape(words.shape[0], words.shape[1] * WORD_BITS)


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    av = _unpack_pm1(a_ref[...])              # (bm, bk*32) bf16
    bv = _unpack_pm1(b_ref[...])              # (bn, bk*32) bf16
    acc_ref[...] += jax.lax.dot_general(
        av, bv, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)   # MXU, f32 accumulate

    @pl.when(k == n_k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("k_valid", "block_m", "block_n", "block_k", "interpret"))
def mxu_pm1_matmul(a: jnp.ndarray, b: jnp.ndarray, *, k_valid: int,
                   block_m: int = 128, block_n: int = 128, block_k: int = 128,
                   interpret: bool = False) -> jnp.ndarray:
    """a: (M, W) int32, b: (N, W) int32 -> +-1 dots (M, N) int32 (Eqn 1).

    Packed padding words unpack to -1 in *both* operands and so contribute
    +1 each to the dot; the correction  dot -= (W*32 - k_valid)  restores
    exactness (pad positions always agree: (-1)*(-1) = +1).
    """
    m, w = a.shape
    n, wb = b.shape
    assert w == wb
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, w)
    gm, gn, gk = pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(w, bk)
    a = jnp.pad(a, ((0, gm * bm - m), (0, gk * bk - w)))
    b = jnp.pad(b, ((0, gn * bn - n), (0, gk * bk - w)))

    out = pl.pallas_call(
        functools.partial(_kernel, n_k_steps=gk),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((gm * bm, gn * bn), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=compiler_params(),
    )(a, b)
    pad_bits = gk * bk * WORD_BITS - k_valid
    return out[:m, :n] - pad_bits
