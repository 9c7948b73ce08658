"""Direct (im2col-free) fused binary convolution: a one- or two-stage chain.

The im2col wrapper around ``fused_conv_bn_binarize`` materializes a
``(N, OH, OW, KH*KW*Cw)`` patch tensor in HBM — KH*KW times the input's
bytes — before the matmul ever runs.  daBNN (1908.05858) and Khan et al.
(1808.00209) both measure that this patch traffic, not the popcounts,
dominates BNN conv time.  The direct form removes it (DESIGN.md §5): each
grid step streams one packed NHWC input tile into VMEM once (halo reads
through element-offset block dims), walks KH x KW as in-VMEM shifted row
reads, and applies the integer threshold + 32-channel bit-pack — and,
with a pool, the OR-pool (max-pool == windowed OR in the packed domain) —
before the single packed store.  Neither the im2col patches nor the
unpacked conv output (nor, with the pool, the pre-pool conv output) ever
reach HBM.

That is exactly a chain of one conv stage (plus one pool stage), so this
entry point builds the stage specs and runs the chain megakernel
(:mod:`repro.kernels.chain_conv`): one kernel body serves both the
per-node ``vpu_direct``/``vpu_direct_pool`` backends and ``vpu_chain``.

Tile knobs — ``block_h`` / ``block_w`` (final output rows/cols per step:
pooled rows when the pool is on) and ``block_n`` (batch images per step)
— are what ``runtime.autotune`` sweeps per node.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.chain_conv import StageSpec, chain_conv


def direct_stages(kh: int, stride: int, pad: int, channels: int,
                  pool_window: int | None = None,
                  pool_stride: int | None = None,
                  pool_pad: tuple[int, int] = (0, 0)
                  ) -> tuple[StageSpec, ...]:
    """The chain spec of one conv (+ OR-pool) node."""
    stages = (StageSpec("conv", kernel=kh, stride=stride, pad_lo=pad,
                        pad_hi=pad, channels=channels),)
    if pool_window is not None:
        stages += (StageSpec("pool", kernel=pool_window,
                             stride=pool_stride or pool_window,
                             pad_lo=pool_pad[0], pad_hi=pool_pad[1],
                             channels=channels),)
    return stages


def direct_conv_bn_binarize(
        x_packed: jnp.ndarray, w_packed: jnp.ndarray,
        threshold: jnp.ndarray, sign_flip: jnp.ndarray,
        *, kh: int, kw: int, stride: int = 1, pad: int = 0,
        word_weights: jnp.ndarray | None = None,
        pool_window: int | None = None, pool_stride: int | None = None,
        pool_pad: tuple[int, int] = (0, 0),
        block_h: int | None = None, block_w: int | None = None,
        block_n: int = 1, interpret: bool = False) -> jnp.ndarray:
    """Direct fused conv(+pool): packed NHWC in, packed NHWC out.

    x_packed: (N, H, W, Cw) int32 channel-packed input (for the bit-plane
        first layer, Cw is the flattened 8*Cw plane-word dim).
    w_packed: (O, KH*KW*Cw) int32 canonical filter layout
        (``binary_conv.pack_conv_weights`` order); square kernels only.
    threshold/sign_flip: (O,) folded integer epilogue (Eqns 5-9).
    word_weights: (KH*KW*Cw,) per-word weights (Eqn 2 bit-plane powers).
    Returns (N, OH', OW', ceil(O/32)) int32 where OH'/OW' are the conv
    output dims, pooled when ``pool_window`` is given.
    """
    if kh != kw:
        raise ValueError(f"direct conv needs a square kernel, got {kh}x{kw}")
    stages = direct_stages(kh, stride, pad, w_packed.shape[0], pool_window,
                           pool_stride, tuple(pool_pad))
    return chain_conv(x_packed, stages,
                      (w_packed, word_weights, threshold, sign_flip),
                      block_h=block_h, block_w=block_w, block_n=block_n,
                      interpret=interpret)
