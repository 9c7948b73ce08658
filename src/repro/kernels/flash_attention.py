"""Pallas TPU kernel: fused flash attention (forward).

Beyond-paper kernel for the framework's LM/DiT/ViT hot spot.  The pure-JAX
chunked attention (models.layers.chunked_attention) is memory-bounded but
its score chain (scores -> mask -> max -> exp -> sum -> PV) still rounds
through HBM between XLA fusions; measured in the dry-run it accounts for
the largest share of LM training's HBM bytes.  This kernel keeps one
(block_q × block_k) f32 score tile + the running (m, l, acc) statistics in
VMEM for an entire KV sweep — the score chain NEVER touches HBM, exactly
the paper's layer-integration philosophy (C4: no intermediate results in
memory) applied to attention.

Grid: (batch·kv_heads·q_groups, S_q/block_q); the kernel loops KV blocks
with lax.fori_loop over dynamic slices of the (S_kv, hd) VMEM-resident
K/V panels.  Causal masking skips fully-masked KV blocks via the loop
upper bound (triangular schedule inside the kernel).

Backward: jax.custom_vjp recomputes through the pure-jnp oracle — exact
gradients, no flash-bwd kernel yet (the TPU deployment would add the
standard dKV/dQ kernels; documented in DESIGN.md).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, causal: bool,
            q_start_base: int, scale: float):
    """One (q-block × full-KV) flash pass.

    q_ref: (block_q, hd); k_ref/v_ref: (S_kv, hd); o_ref: (block_q, hd).
    """
    qi = pl.program_id(1)
    block_q, hd = q_ref.shape
    s_kv = k_ref.shape[0]
    q = q_ref[...].astype(jnp.float32) * scale

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    a0 = jnp.zeros((block_q, hd), jnp.float32)

    q_lo = qi * block_q  # offset of this q block within the q panel

    def body(ki, carry):
        m, l, acc = carry
        k_blk = jax.lax.dynamic_slice_in_dim(k_ref[...], ki * block_k,
                                             block_k, axis=0)
        v_blk = jax.lax.dynamic_slice_in_dim(v_ref[...], ki * block_k,
                                             block_k, axis=0)
        s = jax.lax.dot_general(
            q, k_blk.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, bk)
        if causal:
            q_pos = (q_start_base + q_lo
                     + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 0))
            kv_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= kv_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc * corr[:, None] + pv

    if causal:
        # triangular: this q block attends KV positions
        # [0, q_start_base + q_lo + block_q)
        n_k = (q_start_base + q_lo + block_q + block_k - 1) // block_k
        n_k_max = s_kv // block_k
        # dynamic bound (q_lo is static per grid cell only through
        # program_id) -> fori_loop with traced upper bound
        n_k = jnp.minimum(n_k, n_k_max)
    else:
        n_k = s_kv // block_k
    m, l, acc = jax.lax.fori_loop(0, n_k, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def _flash_fwd(q, k, v, *, causal: bool, block_q: int, block_k: int,
               interpret: bool):
    """q: (B, Sq, H, hd); k/v: (B, Skv, KV, hd) -> (B, Sq, H, hd).

    GQA: H = KV·G; q heads are regrouped so each kernel instance sees its
    single KV head.  Causal assumes Sq == Skv (training/prefill).
    """
    b, sq, h, hd = q.shape
    _, skv, kvh, _ = k.shape
    g = h // kvh
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0
    scale = 1.0 / math.sqrt(hd)

    # (B, KV, G, Sq, hd) -> rows = B·KV·G panels
    qr = jnp.transpose(q.reshape(b, sq, kvh, g, hd),
                       (0, 2, 3, 1, 4)).reshape(b * kvh * g, sq, hd)
    kr = jnp.repeat(
        jnp.transpose(k, (0, 2, 1, 3)).reshape(b * kvh, 1, skv, hd),
        g, axis=1).reshape(b * kvh * g, skv, hd)
    vr = jnp.repeat(
        jnp.transpose(v, (0, 2, 1, 3)).reshape(b * kvh, 1, skv, hd),
        g, axis=1).reshape(b * kvh * g, skv, hd)

    kernel = functools.partial(_kernel, block_k=block_k, causal=causal,
                               q_start_base=0, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(qr.shape[0], sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, hd), lambda r, i: (r, i, 0)),
            pl.BlockSpec((None, skv, hd), lambda r, i: (r, 0, 0)),
            pl.BlockSpec((None, skv, hd), lambda r, i: (r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, hd), lambda r, i: (r, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qr.shape, q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(qr, kr, vr)
    out = out.reshape(b, kvh, g, sq, hd)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, sq, h, hd)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, interpret: bool = False):
    """Fused flash attention (fwd Pallas kernel, recompute-jnp bwd)."""
    return _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                      block_k=block_k, interpret=interpret)


def _fwd(q, k, v, causal, block_q, block_k, interpret):
    out = _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                     block_k=block_k, interpret=interpret)
    return out, (q, k, v)


def _bwd(causal, block_q, block_k, interpret, res, g):
    from repro.models import layers
    q, k, v = res

    def ref(q, k, v):
        return layers.chunked_attention(
            q, k, v, causal=causal, q_chunk=block_q, kv_chunk=block_k)

    _, vjp = jax.vjp(ref, q, k, v)
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)
