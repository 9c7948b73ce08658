"""Mixture-of-Experts layer with explicit expert parallelism (EP).

GSPMD cannot derive an efficient MoE schedule automatically: dense one-hot
dispatch either over-computes every expert for every token (E/k× waste) or
materializes a (tokens × experts × capacity) dispatch tensor.  This module
instead writes the canonical EP collective schedule *explicitly* under
``shard_map``:

  1. route locally (softmax → top-k, capacity-limited scatter into per-expert
     buckets of shape (E, C, D)),
  2. ``all_to_all`` over the ``model`` axis — each shard keeps only its
     E/ep experts but receives that bucket from every peer,
  3. batched expert FFN (one einsum over the local experts),
  4. inverse ``all_to_all``, weighted un-scatter back to token order.

Capacity semantics follow Switch/GShard: per-source-shard capacity
``C = ceil(T_local * k / E * capacity_factor)``; overflow tokens are dropped
(their residual passes through unchanged).  Tests use a high factor to make
the layer exactly match the dense reference.

Expert count padding: if E does not divide the EP degree (granite: 40
experts on 16 shards) the weights are padded to the next multiple (48) and
the router logits of the padding experts are masked to -inf, so they are
never selected and cost only idle FLOPs on 8/48 expert slots.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import Rules
from repro.models import layers



def padded_experts(n_experts: int, ep: int) -> int:
    return -(-n_experts // ep) * ep


def capacity(tokens_local: int, top_k: int, n_experts_padded: int,
             factor: float) -> int:
    c = math.ceil(tokens_local * top_k / n_experts_padded * factor)
    return max(c, 1)


# --------------------------------------------------------------------------
# Local (per-shard) routing + dispatch
# --------------------------------------------------------------------------

def _route(x, router, *, n_real: int, top_k: int):
    """x: (T, D); router: (D, E_pad).  Returns (weights (T,k), ids (T,k),
    probs (T, E_pad)) with padding experts masked out."""
    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
    e_pad = router.shape[1]
    if e_pad != n_real:
        mask = jnp.arange(e_pad) < n_real
        logits = jnp.where(mask[None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = lax.top_k(probs, top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return w, ids, probs


def _dispatch_indices(ids, *, n_experts: int, cap: int):
    """Flat (T*k,) destination slots ``expert*C + position`` with drops.

    Position within each expert's bucket comes from a cumsum over the
    one-hot assignment matrix (order-preserving, deterministic).
    Returns (dest (T*k,) int32 — out-of-range == dropped, keep (T*k,) bool).
    """
    flat = ids.reshape(-1)                                    # (T*k,)
    onehot = (flat[:, None] == jnp.arange(n_experts)[None, :])
    pos = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1    # (T*k, E)
    pos_t = jnp.sum(jnp.where(onehot, pos, 0), axis=1)        # (T*k,)
    keep = pos_t < cap
    dest = flat * cap + pos_t
    dest = jnp.where(keep, dest, n_experts * cap)             # drop sentinel
    return dest, keep


def _expert_ffn(xe, wg, wu, wd, act: str):
    """xe: (El, T, D); weights (El, D, F)/(El, D, F)/(El, F, D)."""
    xe = xe.astype(layers.COMPUTE_DTYPE)
    h_up = jnp.einsum("etd,edf->etf", xe, wu.astype(xe.dtype))
    if act == "swiglu":
        h_gate = jnp.einsum("etd,edf->etf", xe, wg.astype(xe.dtype))
        h = jax.nn.silu(h_gate.astype(jnp.float32)).astype(xe.dtype) * h_up
    elif act == "relu2":
        h = jnp.square(jax.nn.relu(h_up))
    else:
        raise ValueError(act)
    return jnp.einsum("etf,efd->etd", h, wd.astype(xe.dtype))


def _moe_local(x, router, wg, wu, wd, *, n_real: int, top_k: int,
               cap: int, ep_axis: str, all_axes: tuple[str, ...],
               act: str):
    """Per-shard MoE body (runs under shard_map).

    x: (T_local, D); router: (D, E_pad) replicated; wg/wu/wd: local expert
    slices (E_pad/ep, D, F) etc.  Returns (out (T_local, D), aux scalar).
    """
    t_l, d = x.shape
    e_pad = router.shape[1]
    w, ids, probs = _route(x, router, n_real=n_real, top_k=top_k)
    dest, keep = _dispatch_indices(ids, n_experts=e_pad, cap=cap)

    x_rep = jnp.repeat(x, top_k, axis=0)                      # (T*k, D)
    buf = jnp.zeros((e_pad * cap, d), x.dtype)
    buf = buf.at[dest].set(x_rep, mode="drop")                # scatter
    buf = buf.reshape(e_pad, cap, d)

    # EP exchange: keep E_pad/ep experts, receive from all ep peers.
    recv = lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                          tiled=True)                         # (El, ep*C, D)
    y = _expert_ffn(recv, wg, wu, wd, act)
    back = lax.all_to_all(y, ep_axis, split_axis=1, concat_axis=0,
                          tiled=True)                         # (E_pad, C, D)

    back_flat = back.reshape(e_pad * cap, d)
    safe = jnp.minimum(dest, e_pad * cap - 1)
    picked = jnp.where(keep[:, None], back_flat[safe], 0.0)   # (T*k, D)
    out = jnp.sum(
        picked.reshape(t_l, top_k, d)
        * w.astype(picked.dtype)[..., None], axis=1)

    # Switch-style load-balance loss: E * sum_e f_e * p_e, averaged over
    # every shard (all tokens).
    onehot_tok = jax.nn.one_hot(ids, e_pad, dtype=jnp.float32)  # (T,k,E)
    f = jnp.mean(jnp.sum(onehot_tok, axis=1), axis=0)           # (E,)
    p = jnp.mean(probs, axis=0)
    aux = n_real * jnp.sum(f * p)
    aux = lax.pmean(aux, all_axes)
    return out.astype(x.dtype), aux


# --------------------------------------------------------------------------
# Public entry point
# --------------------------------------------------------------------------

def moe_apply(x_tokens: jnp.ndarray, router, wg, wu, wd, *,
              n_experts: int, top_k: int, capacity_factor: float,
              rules: Rules, token_axes, act: str = "swiglu"):
    """Expert-parallel MoE over flat tokens.

    x_tokens: (T, D) with sharding P(token_axes, None).  Expert weights are
    (E_pad, D, F)-shaped with E_pad sharded over ``rules.model``.  When
    ``token_axes`` includes the model axis the EP all_to_all moves disjoint
    token sets; when it does not (decode: too few tokens) the model shards
    route redundantly — correct, and the expert FLOPs at decode are
    negligible.  Returns (out (T, D), aux_loss scalar).
    """
    ep = rules.tp
    e_pad = wg.shape[0]
    assert e_pad % ep == 0, (e_pad, ep)
    t = x_tokens.shape[0]
    token_axes = tuple(token_axes) if token_axes else ()
    t_local = t // max(1, rules.axis_size(token_axes))
    cap = capacity(t_local, top_k, e_pad, capacity_factor)

    body = functools.partial(
        _moe_local, n_real=n_experts, top_k=top_k, cap=cap,
        ep_axis=rules.model,
        all_axes=tuple(rules.mesh.axis_names), act=act)
    if not token_axes:
        tok_axis = None
    elif len(token_axes) == 1:
        tok_axis = token_axes[0]
    else:
        tok_axis = token_axes
    tok_spec = P(tok_axis, None)
    # check_vma=False: when tokens are replicated over the model axis
    # (decode), the static variance checker cannot prove the all_to_all
    # round-trip keeps them replicated; the collectives are still correct.
    out, aux = jax.shard_map(
        body, mesh=rules.mesh,
        in_specs=(tok_spec, P(None, None), P(rules.model, None, None),
                  P(rules.model, None, None), P(rules.model, None, None)),
        out_specs=(tok_spec, P()),
        check_vma=False,
    )(x_tokens, router, wg, wu, wd)
    return out, aux


def moe_reference(x_tokens, router, wg, wu, wd, *, n_experts: int,
                  top_k: int, act: str = "swiglu"):
    """Dense oracle: every expert on every token, then top-k combine.

    No capacity, no drops — the target moe_apply matches when its capacity
    factor is high enough to avoid drops.
    """
    w, ids, _ = _route(x_tokens, router, n_real=n_experts, top_k=top_k)
    all_out = _expert_ffn(
        jnp.broadcast_to(x_tokens, (wg.shape[0],) + x_tokens.shape),
        wg, wu, wd, act)                                       # (E, T, D)
    t = x_tokens.shape[0]
    picked = jnp.take_along_axis(
        jnp.transpose(all_out, (1, 0, 2)), ids[..., None], axis=1)  # (T,k,D)
    return jnp.sum(picked * w.astype(picked.dtype)[..., None], axis=1)
