"""Mamba2 block via state-space duality (SSD), arXiv:2405.21060.

TPU adaptation (DESIGN.md §4): the CUDA implementation is a warp-level
chunked scan; here the *same chunked SSD decomposition* is expressed as

  * intra-chunk: a masked quadratic "attention form" — an MXU matmul over
    (chunk x chunk) tiles;
  * inter-chunk: a `lax.scan` over chunk states (the only sequential part,
    length S/chunk);

which is exactly the structure the `ssd_scan` Pallas kernel implements with
the inter-chunk state carried in VMEM scratch. This module is the jnp
reference/lowering path.

State convention (per head): S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t B_t^T
with A < 0 scalar per head, y_t = S_t C_t + D * x_t.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, rms_norm
from repro.models.partitioning import shard_act


class MambaParams(NamedTuple):
    in_proj: jnp.ndarray  # (d, 2*d_in + 2*N + H)
    conv_w: jnp.ndarray  # (W, conv_dim) depthwise
    conv_b: jnp.ndarray  # (conv_dim,)
    dt_bias: jnp.ndarray  # (H,)
    A_log: jnp.ndarray  # (H,)
    D: jnp.ndarray  # (H,)
    norm: jnp.ndarray  # (d_in,) gated RMSNorm scale
    out_proj: jnp.ndarray  # (d_in, d)


def mamba_dims(cfg):
    d_in = cfg.ssm_expand * cfg.d_model
    n_heads = d_in // cfg.ssm_head_dim
    conv_dim = d_in + 2 * cfg.ssm_state_size
    return d_in, n_heads, conv_dim


def init_mamba_params(key, cfg, dtype) -> MambaParams:
    d_in, n_heads, conv_dim = mamba_dims(cfg)
    ks = jax.random.split(key, 5)
    # inverse-softplus so softplus(dt_bias) spans ~[1e-3, 1e-1]
    dt = jnp.exp(jax.random.uniform(
        ks[0], (n_heads,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))
    a_init = jax.random.uniform(ks[1], (n_heads,), minval=1.0, maxval=16.0)
    return MambaParams(
        in_proj=dense_init(ks[2], (cfg.d_model, 2 * d_in
                                   + 2 * cfg.ssm_state_size + n_heads),
                           dtype=dtype),
        conv_w=(jax.random.normal(ks[3], (cfg.ssm_conv_width, conv_dim))
                / jnp.sqrt(cfg.ssm_conv_width)).astype(dtype),
        conv_b=jnp.zeros((conv_dim,), dtype),
        dt_bias=dt_bias.astype(jnp.float32),
        A_log=jnp.log(a_init).astype(jnp.float32),
        D=jnp.ones((n_heads,), jnp.float32),
        norm=jnp.zeros((d_in,), dtype),
        out_proj=dense_init(ks[4], (d_in, cfg.d_model), dtype=dtype),
    )


def causal_depthwise_conv(x, w, b, state=None, segment_ids=None):
    """x (B,S,C), w (W,C) depthwise causal; state (B,W-1,C) optional history.

    ``segment_ids`` (B,S), each id's tokens contiguous, keeps texts packed
    into one row apart: the tap at offset k reaches token t only where
    token t-k is of t's segment (the history counts as the first
    token's segment). Returns (y (B,S,C), new_state (B,W-1,C)).
    """
    width = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    xx = jnp.concatenate([state, x], axis=1)  # (B, S+W-1, C)
    if segment_ids is None:
        y = sum(xx[:, i: i + x.shape[1], :] * w[i][None, None, :]
                for i in range(width)) + b
    else:
        s = x.shape[1]
        seg = jnp.concatenate(
            [jnp.repeat(segment_ids[:, :1], width - 1, axis=1), segment_ids],
            axis=1)
        y = sum(jnp.where((seg[:, i: i + s] == segment_ids)[..., None],
                          xx[:, i: i + s, :] * w[i][None, None, :], 0)
                for i in range(width)) + b
    new_state = xx[:, -(width - 1):, :] if width > 1 else state
    return y, new_state


def _segsum_decay(dA_chunk):
    """dA_chunk (..., L) log-decays -> (..., L, L) matrix exp(cs_i - cs_j)
    masked to j <= i (else 0)."""
    cs = jnp.cumsum(dA_chunk, axis=-1)  # inclusive
    diff = cs[..., :, None] - cs[..., None, :]  # (..., i, j)
    l = dA_chunk.shape[-1]
    mask = jnp.tril(jnp.ones((l, l), bool))
    return jnp.where(mask, jnp.exp(diff), 0.0)


def ssd_chunked(x, dt, A_log, B, C, D, chunk: int, segment_ids=None):
    """Chunked SSD forward (training / prefill).

    x (b,s,h,p); dt (b,s,h) positive; A_log (h,); B,C (b,s,n); D (h,).
    Returns (y (b,s,h,p), final_state (b,h,p,n)).
    Sequences not divisible by ``chunk`` are zero-padded: dt=0 makes padded
    steps exact identities (decay exp(0)=1, contribution dt*x=0).

    ``segment_ids`` (b,s), each id's tokens contiguous, runs every
    segment of a row as its own sequence from a zero state (the final
    state is the last segment's). The masks are multiplicative, and
    exact because segments are contiguous: token j reaches token i of
    its chunk only if both are of one segment; it reaches the chunk's
    end state only if it is of the segment at the chunk's end; a state
    crosses a chunk only if the segments at both ends of the chunk
    agree; and the state before a chunk reaches token i only if i is of
    the segment at the previous chunk's end.
    """
    b, s_orig, h, p = x.shape
    n = B.shape[-1]
    pad = (-s_orig) % chunk
    if pad:
        padf = lambda a: jnp.pad(  # noqa: E731
            a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        x, dt, B, C = padf(x), padf(dt), padf(B), padf(C)
        if segment_ids is not None:
            segment_ids = jnp.pad(segment_ids, [(0, 0), (0, pad)],
                                  mode="edge")
    s = s_orig + pad
    nc, l = s // chunk, chunk

    f32 = jnp.float32
    a = -jnp.exp(A_log.astype(f32))  # (h,) negative
    dt = dt.astype(f32)
    dA = dt * a[None, None, :]  # (b,s,h) log decay

    xr = x.reshape(b, nc, l, h, p)
    dtr = dt.reshape(b, nc, l, h)
    dAr = dA.reshape(b, nc, l, h).transpose(0, 1, 3, 2)  # (b,nc,h,l)
    Br = B.reshape(b, nc, l, n)
    Cr = C.reshape(b, nc, l, n)

    # ---- intra-chunk (quadratic attention form, MXU-friendly) ----
    decay = _segsum_decay(dAr)  # (b,nc,h,l,l)
    if segment_ids is not None:
        seg = segment_ids.reshape(b, nc, l)
        end = seg[..., -1]  # (b,nc) segment at each chunk's end
        prev_end = jnp.concatenate([end[:, :1], end[:, :-1]], axis=1)
        decay = decay * (seg[..., :, None] == seg[..., None, :])[:, :, None]
    cb = jnp.einsum("bcin,bcjn->bcij", Cr.astype(f32), Br.astype(f32))
    scores = cb[:, :, None] * decay  # (b,nc,h,i,j)
    xdt = xr.astype(f32) * dtr[..., None]  # (b,nc,l,h,p)
    y_intra = jnp.einsum("bchij,bcjhp->bcihp", scores, xdt)

    # ---- chunk states ----
    cs = jnp.cumsum(dAr, axis=-1)  # (b,nc,h,l) inclusive
    total = cs[..., -1]  # (b,nc,h)
    decay_to_end = jnp.exp(total[..., None] - cs)  # (b,nc,h,l)
    if segment_ids is not None:
        decay_to_end = decay_to_end * (seg == end[..., None])[:, :, None]
    # S_chunk = sum_j decay_to_end_j * dt_j * x_j B_j^T  -> (b,nc,h,p,n)
    s_chunk = jnp.einsum("bchj,bcjhp,bcjn->bchpn", decay_to_end, xdt, Br)

    # ---- inter-chunk recurrence over nc (sequential scan) ----
    def step(carry, inp):
        s_prev = carry  # (b,h,p,n) state BEFORE this chunk
        tot, s_c = inp[:2]
        carried = jnp.exp(tot)
        if segment_ids is not None:  # the chunk holds one segment end to end
            carried = carried * inp[2][:, None]
        s_next = carried[..., None, None] * s_prev + s_c
        return s_next, s_prev

    init = jnp.zeros((b, h, p, n), f32)
    xs = (total.transpose(1, 0, 2), s_chunk.transpose(1, 0, 2, 3, 4))
    if segment_ids is not None:
        xs += ((end == prev_end).astype(f32).T,)
    final_state, s_before = jax.lax.scan(step, init, xs)
    s_before = s_before.transpose(1, 0, 2, 3, 4)  # (b,nc,h,p,n)

    # ---- inter-chunk contribution ----
    in_decay = jnp.exp(cs)  # (b,nc,h,l) decay from chunk start to i
    if segment_ids is not None:
        in_decay = in_decay * (seg == prev_end[..., None])[:, :, None]
    y_inter = jnp.einsum("bchi,bcin,bchpn->bcihp", in_decay, Cr.astype(f32),
                         s_before)

    y = y_intra + y_inter + xr.astype(f32) * D[None, None, None, :, None]
    y = y.reshape(b, s, h, p)[:, :s_orig]
    return y.astype(x.dtype), final_state


def ssd_decode_step(x, dt, A_log, B, C, D, state):
    """One-token recurrent update. x (b,1,h,p); state (b,h,p,n)."""
    f32 = jnp.float32
    a = -jnp.exp(A_log.astype(f32))
    dt = dt.astype(f32)[:, 0]  # (b,h)
    dA = jnp.exp(dt * a[None, :])  # (b,h)
    xb = jnp.einsum("bhp,bn->bhpn", x[:, 0].astype(f32) * dt[..., None],
                    B[:, 0].astype(f32))
    new_state = dA[..., None, None] * state + xb
    y = jnp.einsum("bhpn,bn->bhp", new_state, C[:, 0].astype(f32))
    y = y + x[:, 0].astype(f32) * D[None, :, None]
    return y[:, None].astype(x.dtype), new_state


def mamba_block(p: MambaParams, x, cfg, *, ssm_state=None, conv_state=None,
                decode: bool = False, segment_ids=None):
    """Full Mamba2 block. x (B,S,d) -> (y (B,S,d), (ssm_state, conv_state)).

    Training/prefill: decode=False, states returned are final states.
    Decode: decode=True, S must be 1, states are required.
    ``segment_ids`` (B,S) keeps texts packed into one row apart in the
    conv and the scan (``ssd_chunked``); None is one text a row.
    """
    d_in, n_heads, conv_dim = mamba_dims(cfg)
    n = cfg.ssm_state_size
    b, s, _ = x.shape

    zxbcdt = jnp.einsum("bsd,de->bse", x, p.in_proj)
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in: d_in + conv_dim]
    dt_raw = zxbcdt[..., d_in + conv_dim:]  # (b,s,H)

    xbc, new_conv_state = causal_depthwise_conv(
        xbc, p.conv_w, p.conv_b, state=conv_state, segment_ids=segment_ids)
    xbc = jax.nn.silu(xbc.astype(jnp.float32)).astype(xbc.dtype)

    xs = xbc[..., :d_in].reshape(b, s, n_heads, cfg.ssm_head_dim)
    xs = shard_act(xs, ("batch", "seq", "ssm_heads", "hd"))
    B = xbc[..., d_in: d_in + n]
    C = xbc[..., d_in + n:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p.dt_bias[None, None, :])

    if decode:
        y, new_ssm = ssd_decode_step(xs, dt, p.A_log, B, C, p.D, ssm_state)
    else:
        y, new_ssm = ssd_chunked(xs, dt, p.A_log, B, C, p.D, cfg.ssm_chunk,
                                 segment_ids=segment_ids)

    y = y.reshape(b, s, d_in)
    gated = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    gated = rms_norm(gated, p.norm, cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", gated, p.out_proj)
    return out, (new_ssm, new_conv_state)
