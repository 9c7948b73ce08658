"""Jit'd wrappers + execution-path dispatch for the PhoneBit kernels.

This module is the *single* conv/dense dispatch surface: the graph executor
and the flat legacy path both come through here, so every backend shares
one canonical patch-extraction + weight-packing convention
(``repro.core.binary_conv``) instead of parallel implementations.

Conv backends (``CONV_MODES``):

* ``"vpu_direct"``    — direct (im2col-free) fused Pallas kernel: input
                        tiles stream to VMEM once, KHxKW walked as in-VMEM
                        shifted reads, threshold+pack (+ OR-pool) epilogue
                        (DESIGN.md §5).  No patch tensor exists.
* ``"vpu_popcount"``  — paper-faithful xor+popcount Pallas kernel on
                        im2col patches (C1); the legacy im2col path, kept
                        as a selectable backend.
* ``"mxu_pm1"``       — beyond-paper MXU kernel (unpack-to-bf16 in VMEM) on
                        im2col patches; the weighted bit-plane first layer
                        takes the pure-JAX path.
* ``"xla"/"xla_pm1"`` — pure-JAX fallbacks (always available; what
                        benchmarks time on CPU and what models use under
                        jit on any backend).

On CPU the Pallas kernels run with ``interpret=True`` (bit-exact, slow) —
the TPU is the compile target, CPU interpret mode is the validator.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import binary_conv, binary_ops, layer_integration, packing
from repro.kernels import (bitplane_pack as _bitplane_pack_mod,
                           chain_conv as _chain_mod,
                           direct_conv_bn_binarize as _direct_mod,
                           fused_conv_bn_binarize as _fused_mod,
                           mxu_pm1_matmul as _mxu_mod,
                           xnor_popcount_matmul as _xnor_mod)
from repro.core.binary_conv import im2col_matmul

VALID_MODES = ("vpu_popcount", "mxu_pm1", "xla")
# Every engine the fused conv dispatches to; "vpu_direct" is im2col-free,
# the rest ride the canonical im2col lowering.
CONV_MODES = ("xla", "xla_pm1", "mxu_pm1", "vpu_popcount", "vpu_direct")
_IMPL = {"xla": "xor", "xla_pm1": "pm1", "mxu_pm1": "pm1"}


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def binary_matmul_dot(a: jnp.ndarray, b: jnp.ndarray, k_valid: int,
                      mode: str = "vpu_popcount", **block_kw) -> jnp.ndarray:
    """Binary +-1 dots (M, N) int32; dispatches on execution path."""
    if mode == "vpu_popcount":
        cnt = _xnor_mod.xnor_popcount_matmul(
            a, b, interpret=_interpret(), **block_kw)
        return k_valid - 2 * cnt
    if mode == "mxu_pm1":
        return _mxu_mod.mxu_pm1_matmul(
            a, b, k_valid=k_valid, interpret=_interpret(), **block_kw)
    if mode == "xla":
        return binary_ops.packed_matmul_dot(a, b, k_valid)
    raise ValueError(f"unknown matmul mode {mode!r}; want one of {VALID_MODES}")


def matmul_counts(a: jnp.ndarray, b: jnp.ndarray,
                  word_weights: jnp.ndarray | None = None,
                  mode: str = "vpu_popcount", **block_kw) -> jnp.ndarray:
    if mode == "vpu_popcount":
        return _xnor_mod.xnor_popcount_matmul(
            a, b, word_weights, interpret=_interpret(), **block_kw)
    if mode == "xla":
        return binary_ops.packed_matmul_counts(a, b, word_weights=word_weights)
    raise ValueError(f"counts not supported for mode {mode!r}")


def fused_matmul_bn_binarize(a, b, p: layer_integration.IntegratedParams,
                             word_weights=None, mode: str = "vpu_popcount",
                             **block_kw) -> jnp.ndarray:
    """Integrated matmul+BN+sign+pack: (M, ceil(N/32)) int32."""
    if mode == "vpu_popcount":
        return _fused_mod.fused_matmul_bn_binarize(
            a, b, p.threshold, p.sign_flip, word_weights,
            interpret=_interpret(), **block_kw)
    if mode == "mxu_pm1" and word_weights is None:
        # Unweighted layers ride the MXU kernel; the bit-plane first
        # layer's weighted counts take the xor path as ``xla_pm1`` does.
        total = a.shape[-1] * packing.WORD_BITS
        dot = _mxu_mod.mxu_pm1_matmul(a, b, k_valid=total,
                                      interpret=_interpret(), **block_kw)
        bits = layer_integration.apply_threshold((total - dot) // 2, p)
        return packing.pack_bits(bits, axis=-1)
    if mode in _IMPL:
        cnt = binary_ops.packed_matmul_counts(
            a, b, word_weights=word_weights, impl=_IMPL[mode])
        bits = layer_integration.apply_threshold(cnt, p)
        return packing.pack_bits(bits, axis=-1)
    raise ValueError(f"fused path not supported for mode {mode!r}")


def fused_binary_dense(x_packed, w_packed,
                       p: layer_integration.IntegratedParams,
                       mode: str = "vpu_popcount", **block_kw) -> jnp.ndarray:
    """Integrated dense+BN+binarize on flattened packed input, any mode."""
    flat = x_packed.reshape(x_packed.shape[0], -1)
    return fused_matmul_bn_binarize(flat, w_packed, p, mode=mode, **block_kw)


def fused_binary_conv2d(x_packed: jnp.ndarray, w_packed: jnp.ndarray,
                        p: layer_integration.IntegratedParams,
                        kh: int, kw: int, stride: int = 1, pad: int = 0,
                        word_weights=None, mode: str = "vpu_popcount",
                        pool: tuple[int, int, tuple[int, int]] | None = None,
                        **block_kw) -> jnp.ndarray:
    """Fused conv+BN+binarize(+OR-pool) dispatch — one call site for every
    backend (C4+C6).

    ``pool`` is an optional ``(window, stride, (pad_lo, pad_hi))`` OR-pool
    epilogue.  On ``"vpu_direct"`` it fuses into the kernel epilogue (the
    pre-pool conv output never reaches HBM); on the im2col backends it runs
    as a separate packed-domain OR-pool after the conv.
    """
    if mode == "vpu_direct":
        pool_kw = {}
        if pool is not None:
            pool_kw = dict(pool_window=pool[0], pool_stride=pool[1],
                           pool_pad=tuple(pool[2]))
        return _direct_mod.direct_conv_bn_binarize(
            x_packed, w_packed, p.threshold, p.sign_flip,
            kh=kh, kw=kw, stride=stride, pad=pad,
            word_weights=word_weights, interpret=_interpret(),
            **pool_kw, **block_kw)
    if mode in ("vpu_popcount", "mxu_pm1"):
        flat, (n, oh, ow) = im2col_matmul(x_packed, kh, kw, stride, pad)
        out = fused_matmul_bn_binarize(
            flat, w_packed, p, word_weights=word_weights, mode=mode,
            **block_kw)
        out = out.reshape(n, oh, ow, out.shape[-1])
    elif mode in _IMPL:
        out = binary_conv.binary_conv2d_fused(
            x_packed, w_packed, p, kh, kw, stride, pad,
            word_weights=word_weights, impl=_IMPL[mode])
    else:
        raise ValueError(
            f"unknown conv mode {mode!r}; want one of {CONV_MODES}")
    if pool is not None:
        out = binary_conv.binary_or_maxpool(out, pool[0], pool[1],
                                            pad=tuple(pool[2]))
    return out


def chain_forward(x_packed: jnp.ndarray, stages, stage_arrays,
                  **kw) -> jnp.ndarray:
    """Run a fused conv/pool chain (one region) in a single megakernel
    call with VMEM-resident intermediates (DESIGN.md §9); the region-level
    counterpart of :func:`fused_binary_conv2d`."""
    return _chain_mod.chain_conv(x_packed, tuple(stages),
                                 tuple(stage_arrays),
                                 interpret=_interpret(), **kw)


def bitplane_pack(x: jnp.ndarray, **kw) -> jnp.ndarray:
    """(N,H,W,C) uint8 -> (N,H,W,8*Cw) packed planes via the Pallas kernel."""
    return _bitplane_pack_mod.bitplane_pack(x, interpret=_interpret(), **kw)
