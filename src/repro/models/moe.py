"""Mixture-of-Experts MLP with top-k routing and capacity-bounded
scatter/gather dispatch.

Design notes (TPU adaptation, see DESIGN.md §4):

* Dispatch is **sort/scatter based**, not the Shazeer one-hot-einsum
  dispatch: the einsum form counts T*E*C*d fake MAC FLOPs that would
  dominate `cost_analysis()` for fine-grained experts (granite d_ff=512)
  and poison the roofline. Scatter moves exactly T*k*d bytes — the honest
  cost.
* Expert compute is a single batched einsum (E, C, d) x (E, d, ff): MXU
  friendly, and GSPMD shards C over `data` and ff over `model`
  (expert-data parallelism + tensor-parallel experts). When E divides the
  model axis the weights may instead be expert-sharded; the sharding rules
  in `launch/sharding.py` pick per-arch.
* Tokens overflowing expert capacity C = ceil(T*k/E) * capacity_factor are
  dropped (standard dropping MoE); the router aux loss keeps load balanced
  so drops are rare.
* ``moe_dropless`` drops nothing: the (token, expert) pairs are sorted by
  expert and run through grouped products (``jax.lax.ragged_dot``) over
  the experts this chip holds. The router scores every expert; the pairs
  of experts held elsewhere add nothing here (expert parallelism without
  its exchange, the chip's share of the layer).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.partitioning import logical_axis_size, shard_act

CAPACITY_FACTOR = 1.25


def _shard_moe(x):
    """(E, C, d): experts over model when divisible, capacity over data."""
    return shard_act(x, ("experts", "capacity", "embed"))


def _shard_moe_blocked(x):
    """(nb, E, C_local, d): token blocks over data, experts over model."""
    return shard_act(x, ("capacity", "experts", None, "embed"))


class MoEParams(NamedTuple):
    router: jnp.ndarray  # (d, E)
    w_gate: jnp.ndarray  # (E, d, ff)
    w_up: jnp.ndarray  # (E, d, ff)
    w_down: jnp.ndarray  # (E, ff, d)


def init_moe_params(key, cfg, dtype) -> MoEParams:
    """The router over all ``num_experts``; the weights of the experts
    held here (``num_experts_held``)."""
    from repro.models.layers import dense_init

    ks = jax.random.split(key, 4)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    held = cfg.num_experts_held
    return MoEParams(
        router=dense_init(ks[0], (d, e), dtype=jnp.float32),
        w_gate=dense_init(ks[1], (held, d, f), in_axis=1, dtype=dtype),
        w_up=dense_init(ks[2], (held, d, f), in_axis=1, dtype=dtype),
        w_down=dense_init(ks[3], (held, f, d), in_axis=1, dtype=dtype),
    )


def expert_capacity(num_tokens: int, num_experts: int, k: int,
                    factor: float = CAPACITY_FACTOR) -> int:
    cap = int(num_tokens * k / num_experts * factor) + 1
    # MXU-align the capacity dimension
    return max(8, -(-cap // 8) * 8)


def _dispatch_block(xf, topk_idx, num_experts: int, k: int, cap: int):
    """Scatter one token block into its (E, cap, d) expert buffer.

    Returns (expert_in, target, token_of_pair, keep). vmapped over token
    blocks so that, with blocks laid out on the `data` axis, the scatter is
    shard-local — a replicated dispatch buffer would otherwise be
    all-reduced across every data shard.
    """
    t, d = xf.shape
    flat_expert = topk_idx.reshape(-1)  # (T*k,)
    onehot = jax.nn.one_hot(flat_expert, num_experts, dtype=jnp.int32)
    pos_in_expert = jnp.cumsum(onehot, axis=0) - onehot  # (T*k, E)
    slot = jnp.take_along_axis(pos_in_expert, flat_expert[:, None],
                               axis=1)[:, 0]
    keep = slot < cap
    target = jnp.where(keep, flat_expert * cap + slot, num_experts * cap)
    token_of_pair = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((num_experts * cap + 1, d), xf.dtype)
    buf = buf.at[target].set(xf[token_of_pair])
    expert_in = buf[: num_experts * cap].reshape(num_experts, cap, d)
    return expert_in, target, token_of_pair, keep


def _combine_block(expert_out, target, token_of_pair, keep, topk_probs,
                   t: int):
    """Gather one block's expert outputs back to token order (weighted)."""
    e, cap, d = expert_out.shape
    flat_out = expert_out.reshape(e * cap, d)
    flat_out = jnp.concatenate(
        [flat_out, jnp.zeros((1, d), flat_out.dtype)], axis=0)
    pair_out = flat_out[target]
    w = (topk_probs.reshape(-1) * keep).astype(pair_out.dtype)
    contrib = pair_out * w[:, None]
    return jnp.zeros((t, d), expert_out.dtype).at[token_of_pair].add(contrib)


def moe_ffn(p: MoEParams, x: jnp.ndarray, num_experts: int, k: int,
            aux_coef: float = 0.01, capacity_factor: float = CAPACITY_FACTOR):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    Fully jit/SPMD compatible: fixed shapes, no ragged ops. Tokens are
    dispatched in ``nb`` = data-axis-size independent blocks (nb=1 without
    a sharding context) so dispatch/combine scatters stay shard-local.
    """
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)

    logits = (xf.astype(jnp.float32) @ p.router)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_probs, topk_idx = jax.lax.top_k(probs, k)  # (T, k)
    # renormalize the chosen experts' weights (mixtral/grok convention)
    topk_probs = topk_probs / jnp.maximum(
        topk_probs.sum(-1, keepdims=True), 1e-9)

    # ---- load-balance aux loss (Switch-style, global) ----
    me = probs.mean(axis=0)  # (E,) mean router prob
    one_hot_top1 = jax.nn.one_hot(topk_idx[:, 0], num_experts)
    ce = one_hot_top1.mean(axis=0)  # (E,) fraction of tokens (top-1)
    aux = aux_coef * num_experts * jnp.sum(me * ce)

    # ---- block-local dispatch ----
    nb = logical_axis_size("capacity")
    if nb <= 1 or t % nb != 0:
        nb = 1
    tl = t // nb
    cap = expert_capacity(tl, num_experts, k, capacity_factor)
    xb = xf.reshape(nb, tl, d)
    ib = topk_idx.reshape(nb, tl, k)
    pb = topk_probs.reshape(nb, tl, k)
    expert_in, target, token_of_pair, keep = jax.vmap(
        lambda xx, ii: _dispatch_block(xx, ii, num_experts, k, cap))(xb, ib)
    expert_in = _shard_moe_blocked(expert_in)  # (nb, E, cap, d)

    # ---- expert FFN (SwiGLU), batched over blocks ----
    g = jnp.einsum("necd,edf->necf", expert_in, p.w_gate)
    u = jnp.einsum("necd,edf->necf", expert_in, p.w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(u.dtype) * u
    expert_out = jnp.einsum("necf,efd->necd", h, p.w_down)
    expert_out = _shard_moe_blocked(expert_out)

    # ---- combine per block ----
    out = jax.vmap(
        lambda eo, tg, tp, kp, w: _combine_block(eo, tg, tp, kp, w, tl))(
        expert_out, target, token_of_pair, keep, pb)
    return out.reshape(b, s, d).astype(x.dtype), aux


def route(p: MoEParams, xf: jnp.ndarray, k: int, aux_coef: float):
    """xf (T, d) -> (top_idx (T, k), gates (T, k), aux_loss): the top
    ``k`` of the router's logits over every expert, gated by a softmax
    over those ``k``, and the Switch-style load-balance loss."""
    e = p.router.shape[1]
    logits = jnp.einsum("td,de->te", xf, p.router,
                        preferred_element_type=jnp.float32)
    top_logits, top_idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top_logits, axis=-1)
    probs = jax.nn.softmax(logits, axis=-1)
    ce = jax.nn.one_hot(top_idx[:, 0], e).mean(axis=0)
    aux = aux_coef * e * jnp.sum(probs.mean(axis=0) * ce)
    return top_idx, gates, aux


def moe_dropless(p: MoEParams, x: jnp.ndarray, k: int,
                 aux_coef: float = 0.01, first: int = 0):
    """x: (B, S, d) -> (out (B, S, d), aux_loss, rows).

    Each token is routed over all ``p.router.shape[1]`` experts
    (``route``). ``p`` holds the weights of experts ``first .. first +
    E_held - 1``; the result is what those experts give, for every token
    routed to them (none is dropped). ``rows`` counts the (token,
    expert) pairs handed to the grouped products.
    """
    b, s, d = x.shape
    t = b * s
    held = p.w_gate.shape[0]
    xf = x.reshape(t, d)
    top_idx, gates, aux = route(p, xf, k, aux_coef)

    # pairs of held experts first, sorted by expert; the rest after them
    local = top_idx.reshape(-1) - first
    is_held = (local >= 0) & (local < held)
    group = jnp.where(is_held, local, held)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    rows = jnp.sum(sizes)
    gate = jnp.where(is_held, gates.reshape(-1), 0.0)[order]

    xs = xf[order // k]  # (T*k, d): the token of each sorted pair
    g = jax.lax.ragged_dot(xs, p.w_gate, sizes)
    u = jax.lax.ragged_dot(xs, p.w_up, sizes)
    h = jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
    h = (h * gate[:, None]).astype(xs.dtype)
    y = jax.lax.ragged_dot(h, p.w_down, sizes)
    # rows past the held pairs belong to no group: whatever the grouped
    # product left there is not a result
    y = jnp.where((jnp.arange(t * k) < rows)[:, None], y, 0)
    inv = jnp.argsort(order)
    out = jnp.sum(y[inv].reshape(t, k, d).astype(jnp.float32), axis=1)
    return out.reshape(b, s, d).astype(x.dtype), aux, rows


def shared_expert(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """The always-on shared expert: a SwiGLU MLP over every token."""
    from repro.models.layers import swiglu

    return swiglu(x, p["w_gate"], p["w_up"], p["w_down"])
