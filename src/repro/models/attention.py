"""GQA attention with windowing, softcapping, qk-norm, and KV caching.

One attention implementation serves every assigned arch:

* GQA via head-grouped einsum (never materializes repeated KV in HBM);
* window may be a *traced* per-layer scalar, so local/global alternating
  patterns (gemma2 1:1, gemma3 5:1) ride a single ``lax.scan`` over layers
  with the window as scan-xs — this is what keeps the HLO small enough to
  compile 62-layer models quickly;
* decode attends one query against a pre-allocated cache with validity
  masking (positions >= cache_pos are masked).

The Pallas flash kernel (`repro.kernels.flash_attention`) implements the
same contract for the TPU target; this jnp path is the oracle and the
dry-run lowering path.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import rms_norm, rope, softcap
from repro.models.partitioning import (
    prefers_q_sharding,
    prefers_repeat_kv,
    shard_act,
)

NEG_INF = -2.3819763e38  # bf16-safe large negative


class AttnParams(NamedTuple):
    wq: jnp.ndarray  # (d, H*hd)
    wk: jnp.ndarray  # (d, KV*hd)
    wv: jnp.ndarray  # (d, KV*hd)
    wo: jnp.ndarray  # (H*hd, d)
    bq: Optional[jnp.ndarray]
    bk: Optional[jnp.ndarray]
    bv: Optional[jnp.ndarray]
    q_norm: Optional[jnp.ndarray]  # (hd,)
    k_norm: Optional[jnp.ndarray]  # (hd,)


def init_attn_params(key, cfg, dtype) -> AttnParams:
    from repro.models.layers import dense_init

    ks = jax.random.split(key, 4)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    zeros = lambda n: jnp.zeros((n,), dtype)  # noqa: E731
    return AttnParams(
        wq=dense_init(ks[0], (d, qd), dtype=dtype),
        wk=dense_init(ks[1], (d, kvd), dtype=dtype),
        wv=dense_init(ks[2], (d, kvd), dtype=dtype),
        wo=dense_init(ks[3], (qd, d), dtype=dtype),
        bq=zeros(qd) if cfg.qkv_bias else None,
        bk=zeros(kvd) if cfg.qkv_bias else None,
        bv=zeros(kvd) if cfg.qkv_bias else None,
        q_norm=jnp.zeros((cfg.head_dim,), dtype) if cfg.qk_norm else None,
        k_norm=jnp.zeros((cfg.head_dim,), dtype) if cfg.qk_norm else None,
    )


def _project_qkv(p: AttnParams, x, num_heads, num_kv, head_dim, eps):
    b, s, _ = x.shape
    q = jnp.einsum("bsd,de->bse", x, p.wq)
    k = jnp.einsum("bsd,de->bse", x, p.wk)
    v = jnp.einsum("bsd,de->bse", x, p.wv)
    if p.bq is not None:
        q, k, v = q + p.bq, k + p.bk, v + p.bv
    q = shard_act(q.reshape(b, s, num_heads, head_dim),
                  ("batch", "seq", "heads", "hd"))
    k = shard_act(k.reshape(b, s, num_kv, head_dim),
                  ("batch", "seq", "kv_heads", "hd"))
    v = shard_act(v.reshape(b, s, num_kv, head_dim),
                  ("batch", "seq", "kv_heads", "hd"))
    if p.q_norm is not None:
        q = rms_norm(q, p.q_norm, eps)
        k = rms_norm(k, p.k_norm, eps)
    return q, k, v


def _rope_qk(q, k, positions, theta, cfg):
    """RoPE on q and k; nothing where the config has no positional
    encoding (NoPE)."""
    if not cfg.use_rope:
        return q, k
    return rope(q, positions, theta), rope(k, positions, theta)


def gqa_scores_softmax(q, k, v, mask, logit_cap, scale=None):
    """q (b,sq,H,hd), k/v (b,sk,KV,hd), mask (b,1 or KV*G? , sq, sk) bool.

    Scores are multiplied by ``scale`` (None: 1 / sqrt(hd)).
    Returns (b, sq, H, hd). Softmax in f32.
    """
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    if kv != h and prefers_repeat_kv(h, kv):
        # repeated-KV layout: keeps one shardable 'heads' dim when the
        # grouped form would force score replication (see partitioning.py)
        g = h // kv
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        kv = h
    if kv == h:
        from repro.models.partitioning import logical_axis_size

        h_ok = h % max(logical_axis_size("heads"), 1) == 0
        if not h_ok:  # MHA with non-divisible heads: q-sequence shard
            q = shard_act(q, ("batch", "seq_q", None, None))
        scores = jnp.einsum("bqhd,bshd->bhqs", q, k).astype(jnp.float32)
        scores = shard_act(scores, ("batch", "heads", None, None) if h_ok
                           else ("batch", None, "seq_q", None))
        scores = _scaled(scores, hd, scale)
        if logit_cap is not None:
            scores = softcap(scores, logit_cap)
        scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhqs,bshd->bqhd", probs, v)
        return shard_act(out, ("batch", "seq", "heads", "hd"))
    g = h // kv
    q_sharded = prefers_q_sharding(h, kv)
    if q_sharded:
        q = shard_act(q, ("batch", "seq_q", None, None))
    qg = q.reshape(b, sq, kv, g, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    scores = shard_act(scores, ("batch", "kv_heads", None,
                                "seq_q" if q_sharded else None, None))
    scores = _scaled(scores, hd, scale)
    if logit_cap is not None:
        scores = softcap(scores, logit_cap)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    out = out.reshape(b, sq, h, hd)
    return shard_act(out, ("batch", "seq", "heads", "hd"))


def _scaled(scores, hd, scale):
    if scale is None:
        return scores / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    return scores * jnp.asarray(scale, jnp.float32)


def segment_positions(segment_ids):
    """(b, s) segment ids, each id's tokens contiguous -> each token's
    position within its segment (0 at a segment's first token)."""
    idx = jnp.arange(segment_ids.shape[1], dtype=jnp.int32)
    first = jnp.concatenate(
        [jnp.ones_like(segment_ids[:, :1], bool),
         segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1)
    return idx - jax.lax.cummax(jnp.where(first, idx, 0), axis=1)


def make_causal_window_mask(q_pos, k_pos, window, k_valid=None):
    """bool mask (b?, sq, sk). window traced scalar; <=0 means global."""
    causal = k_pos[..., None, :] <= q_pos[..., :, None]
    dist = q_pos[..., :, None] - k_pos[..., None, :]
    win = jnp.asarray(window, jnp.int32)
    inside = jnp.where(win > 0, dist < win, True)
    mask = causal & inside
    if k_valid is not None:
        mask = mask & k_valid[..., None, :]
    return mask


# sequences >= this use the q-chunked (flash-style) XLA path: scores for a
# 32k prefill would otherwise materialize B*H*S^2 f32 (hundreds of GB/chip)
CHUNK_THRESHOLD = 8192
Q_CHUNK = 1024


def _chunked_gqa(q, k, v, window, logit_cap, q_chunk: int, scale=None,
                 segment_ids=None):
    """Causal/windowed attention, scanning q in chunks of ``q_chunk``.

    q (b,s,h,hd) and k/v (b,s,kv,hd) are already roped. Peak score memory
    drops from O(S^2) to O(q_chunk * S) — the XLA-level analogue of the
    Pallas flash kernel (which replaces this on real TPUs). A query sees
    only keys of its own segment where ``segment_ids`` (b,s) is given.
    """
    b, s, h, hd = q.shape
    assert s % q_chunk == 0, (s, q_chunk)
    nq = s // q_chunk
    qc = q.reshape(b, nq, q_chunk, h, hd).transpose(1, 0, 2, 3, 4)
    k_pos = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)

    def body(_, inp):
        qblk, i = inp  # (b, qc, h, hd), scalar chunk index
        q_pos = (i * q_chunk
                 + jnp.arange(q_chunk, dtype=jnp.int32))[None, :].repeat(b, 0)
        mask = make_causal_window_mask(q_pos, k_pos, window)
        if segment_ids is not None:
            seg_q = jax.lax.dynamic_slice_in_dim(segment_ids, i * q_chunk,
                                                 q_chunk, axis=1)
            mask = mask & (seg_q[:, :, None] == segment_ids[:, None, :])
        out = gqa_scores_softmax(qblk, k, v, mask, logit_cap, scale)
        return None, out

    _, outs = jax.lax.scan(body, None, (qc, jnp.arange(nq)))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)


def attend_full(p: AttnParams, x, cfg, *, window, theta, positions=None,
                segment_ids=None):
    """Training / encoder-free full-sequence self attention (no cache).

    positions defaults to arange; window/theta may be traced scalars.
    ``segment_ids`` (b,s), each id's tokens contiguous, packs several
    texts into a row: a query attends only to earlier keys of its own
    segment, and positions (for RoPE and the window) restart at each
    segment's first token unless given.
    """
    b, s, _ = x.shape
    if positions is None:
        positions = (jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
                     if segment_ids is None
                     else segment_positions(segment_ids))
    q, k, v = _project_qkv(p, x, cfg.num_heads, cfg.num_kv_heads,
                           cfg.head_dim, cfg.norm_eps)
    q, k = _rope_qk(q, k, positions, theta, cfg)
    if s >= CHUNK_THRESHOLD and s % Q_CHUNK == 0:
        out = _chunked_gqa(q, k, v, window, cfg.attn_logit_softcap, Q_CHUNK,
                           cfg.attn_scale, segment_ids)
    else:
        mask = make_causal_window_mask(positions, positions, window)
        if segment_ids is not None:
            mask = mask & (segment_ids[:, :, None] == segment_ids[:, None, :])
        out = gqa_scores_softmax(q, k, v, mask, cfg.attn_logit_softcap,
                                 cfg.attn_scale)
    return jnp.einsum("bse,ed->bsd", out.reshape(b, s, -1), p.wo)


def prefill(p: AttnParams, x, cfg, *, window, theta, cache_len):
    """Full-sequence attention that also materializes the KV cache.

    Returns (out (b,s,d), k_cache (b,cache_len,KV,hd), v_cache).
    """
    b, s, _ = x.shape
    positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
    q, k, v = _project_qkv(p, x, cfg.num_heads, cfg.num_kv_heads,
                           cfg.head_dim, cfg.norm_eps)
    q, k = _rope_qk(q, k, positions, theta, cfg)
    if s >= CHUNK_THRESHOLD and s % Q_CHUNK == 0:
        out = _chunked_gqa(q, k, v, window, cfg.attn_logit_softcap, Q_CHUNK,
                           cfg.attn_scale)
    else:
        mask = make_causal_window_mask(positions, positions, window)
        out = gqa_scores_softmax(q, k, v, mask, cfg.attn_logit_softcap,
                                 cfg.attn_scale)
    out = jnp.einsum("bse,ed->bsd", out.reshape(b, s, -1), p.wo)
    pad = cache_len - s
    if pad > 0:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return out, k, v


def decode_step(p: AttnParams, x, k_cache, v_cache, cache_pos, cfg, *,
                window, theta):
    """One-token decode. x (b,1,d); caches (b,S,KV,hd); cache_pos scalar.

    Writes the new KV at cache_pos, attends against positions < cache_pos+1.
    Returns (out (b,1,d), k_cache, v_cache).
    """
    b = x.shape[0]
    s_max = k_cache.shape[1]
    pos = jnp.full((b, 1), cache_pos, jnp.int32)
    q, k, v = _project_qkv(p, x, cfg.num_heads, cfg.num_kv_heads,
                           cfg.head_dim, cfg.norm_eps)
    q, k = _rope_qk(q, k, pos, theta, cfg)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, cache_pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, cache_pos, axis=1)
    k_pos = jnp.arange(s_max, dtype=jnp.int32)[None, :].repeat(b, 0)
    k_valid = k_pos <= cache_pos  # includes the token just written
    mask = make_causal_window_mask(pos, k_pos, window, k_valid=k_valid)
    out = gqa_scores_softmax(q, k_cache, v_cache, mask, cfg.attn_logit_softcap,
                             cfg.attn_scale)
    out = jnp.einsum("bse,ed->bsd", out.reshape(b, 1, -1), p.wo)
    return out, k_cache, v_cache


def ring_decode_step(p: AttnParams, x, k_cache, v_cache, cache_pos, cfg, *,
                     window: int, theta):
    """One-token decode against a RING cache of ``window`` slots.

    The cache holds the last ``window`` (roped) keys/values at slot
    ``pos % window``; slot s currently stores true position
    ``pos - (slot - s)`` if s <= slot else ``pos - (slot + window - s)``,
    which is always within (pos - window, pos] — so the sliding-window +
    causal mask reduces to ``true_pos >= 0`` (unfilled slots).

    Memory: O(window) instead of O(context) per local layer — for gemma3's
    5:1 pattern at 32k that removes 97% of local-layer cache traffic.
    """
    b = x.shape[0]
    w = k_cache.shape[1]
    pos = jnp.full((b, 1), cache_pos, jnp.int32)
    q, k, v = _project_qkv(p, x, cfg.num_heads, cfg.num_kv_heads,
                           cfg.head_dim, cfg.norm_eps)
    q, k = _rope_qk(q, k, pos, theta, cfg)
    slot = jnp.asarray(cache_pos, jnp.int32) % w
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, slot, axis=1)
    s = jnp.arange(w, dtype=jnp.int32)
    true_pos = jnp.where(s <= slot,
                         cache_pos - (slot - s),
                         cache_pos - (slot + w - s))
    mask = (true_pos >= 0)[None, None, :].repeat(b, 0)  # (b, 1, w)
    out = gqa_scores_softmax(q, k_cache, v_cache, mask,
                             cfg.attn_logit_softcap, cfg.attn_scale)
    out = jnp.einsum("bse,ed->bsd", out.reshape(b, 1, -1), p.wo)
    return out, k_cache, v_cache


# ---------------------------------------------------------------------------
# Cross attention (whisper decoder -> encoder states)
# ---------------------------------------------------------------------------
class CrossAttnParams(NamedTuple):
    wq: jnp.ndarray
    wk: jnp.ndarray
    wv: jnp.ndarray
    wo: jnp.ndarray


def init_cross_attn_params(key, cfg, dtype) -> CrossAttnParams:
    from repro.models.layers import dense_init

    ks = jax.random.split(key, 4)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    return CrossAttnParams(
        wq=dense_init(ks[0], (d, qd), dtype=dtype),
        wk=dense_init(ks[1], (d, kvd), dtype=dtype),
        wv=dense_init(ks[2], (d, kvd), dtype=dtype),
        wo=dense_init(ks[3], (qd, d), dtype=dtype),
    )


def cross_kv(p: CrossAttnParams, enc_out, cfg):
    """Precompute (k, v) for the encoder memory (done once at prefill)."""
    b, s, _ = enc_out.shape
    k = jnp.einsum("bsd,de->bse", enc_out, p.wk).reshape(
        b, s, cfg.num_kv_heads, cfg.head_dim)
    v = jnp.einsum("bsd,de->bse", enc_out, p.wv).reshape(
        b, s, cfg.num_kv_heads, cfg.head_dim)
    return k, v


def cross_attend(p: CrossAttnParams, x, k, v, cfg):
    b, sq, _ = x.shape
    sk = k.shape[1]
    q = jnp.einsum("bsd,de->bse", x, p.wq).reshape(
        b, sq, cfg.num_heads, cfg.head_dim)
    mask = jnp.ones((b, sq, sk), bool)
    out = gqa_scores_softmax(q, k, v, mask, None)
    return jnp.einsum("bse,ed->bsd", out.reshape(b, sq, -1), p.wo)


def encoder_self_attend(p: AttnParams, x, cfg):
    """Bidirectional (encoder) self attention, sinusoid-free (RoPE)."""
    b, s, _ = x.shape
    positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
    q, k, v = _project_qkv(p, x, cfg.num_heads, cfg.num_kv_heads,
                           cfg.head_dim, cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    mask = jnp.ones((b, s, s), bool)
    out = gqa_scores_softmax(q, k, v, mask, cfg.attn_logit_softcap)
    return jnp.einsum("bse,ed->bsd", out.reshape(b, s, -1), p.wo)
