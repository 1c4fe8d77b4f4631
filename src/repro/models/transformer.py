"""Backbone assembler: one init/forward pair covering all ten assigned
architectures.

Key structural decisions (they determine compile time and shardability):

* **Scan over layers with stacked parameters.** All per-layer params are
  stacked on a leading L axis and the depth loop is one ``jax.lax.scan`` —
  a 64-layer grok-1 lowers to the same HLO size as a 2-layer model.
* **Per-layer heterogeneity rides scan-xs**, not Python branching: window
  sizes (gemma 1:1 and 5:1 local:global alternation) and rope thetas are
  (L,)-arrays consumed as traced scalars by the layer body.
* **KV caches are scan xs/ys**: each layer reads its cache slice and emits
  the updated slice; the stacked cache (L, B, S, KV, hd) shards over the
  mesh (S over `data` for batch-1 long context, KV-heads over `model`).
* **Hybrid (zamba2)** is a scan over super-blocks: ``shared_attn_every``
  Mamba2 trunk layers + one application of the *shared-weight* attention
  block (closure params — the defining Zamba2 trick), with a scanned tail
  for the remainder.
* **Mixed (granite-4.0-h)**: Mamba2 and attention layers with their own
  weights, each followed by its MoE. Parameters are stacked per kind;
  the depth loop is a scan over whole periods of ``block_pattern``, and
  inside a period each run of same-kind layers is a scan of its own. The
  decode cache holds the Mamba layers' SSM and conv states and the
  attention layers' K/V side by side.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ATTN, GLOBAL, MAMBA, ModelConfig
from repro.models import attention as attn_mod
from repro.models.attention import (
    AttnParams,
    CrossAttnParams,
    cross_attend,
    cross_kv,
    encoder_self_attend,
    init_attn_params,
    init_cross_attn_params,
)
from repro.models.layers import dense_init, rms_norm, softcap, swiglu
from repro.models.partitioning import shard_act
from repro.models.moe import (
    MoEParams,
    init_moe_params,
    moe_dropless,
    moe_ffn,
    shared_expert,
)
from repro.models.ssm import (
    MambaParams,
    init_mamba_params,
    mamba_block,
    mamba_dims,
)

PyTree = Any


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------
def _init_mlp(key, d, ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d, ff), dtype=dtype),
        "w_up": dense_init(k2, (d, ff), dtype=dtype),
        "w_down": dense_init(k3, (ff, d), dtype=dtype),
    }


def _init_ffn(key, cfg: ModelConfig, dtype) -> dict:
    """The norm and MLP (dense or MoE, and the shared expert) after a
    mixer."""
    km, ks = jax.random.split(key)
    layer = {"ln2": jnp.zeros((cfg.d_model,), dtype)}
    if cfg.is_moe:
        layer["moe"] = init_moe_params(km, cfg, dtype)
    else:
        layer["mlp"] = _init_mlp(km, cfg.d_model, cfg.d_ff, dtype)
    if cfg.shared_expert_ff:
        layer["shared"] = _init_mlp(ks, cfg.d_model, cfg.shared_expert_ff,
                                    dtype)
    return layer


def _init_attn_layer(cfg: ModelConfig, dtype, with_cross: bool):
    def init_one(key):
        ka, km, kc = jax.random.split(key, 3)
        layer = {
            "ln1": jnp.zeros((cfg.d_model,), dtype),
            "attn": init_attn_params(ka, cfg, dtype),
            **_init_ffn(km, cfg, dtype),
        }
        if cfg.use_post_norm:
            layer["post_ln1"] = jnp.zeros((cfg.d_model,), dtype)
            layer["post_ln2"] = jnp.zeros((cfg.d_model,), dtype)
        if with_cross:
            layer["ln_cross"] = jnp.zeros((cfg.d_model,), dtype)
            layer["cross"] = init_cross_attn_params(kc, cfg, dtype)
        return layer

    return init_one


def _init_mamba_layer(cfg: ModelConfig, dtype):
    def init_one(key):
        km, kf = jax.random.split(key)
        layer = {
            "ln": jnp.zeros((cfg.d_model,), dtype),
            "mamba": init_mamba_params(km, cfg, dtype),
        }
        if cfg.is_mixed:
            layer.update(_init_ffn(kf, cfg, dtype))
        return layer

    return init_one


def init_params(cfg: ModelConfig, key) -> PyTree:
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    params: dict = {}

    params["embed"] = (
        jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model)) * 0.02
    ).astype(dtype)
    params["final_norm"] = jnp.zeros((cfg.d_model,), dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[1], (cfg.d_model, cfg.vocab_size),
                                       dtype=dtype)

    kinds = cfg.layer_kinds()
    n_layers = cfg.num_layers
    layer_keys = jax.random.split(keys[2], n_layers)

    if all(k == ATTN for k in kinds):
        init_one = _init_attn_layer(cfg, dtype, with_cross=cfg.is_encoder_decoder)
        params["layers"] = jax.vmap(init_one)(layer_keys)
    elif all(k == MAMBA for k in kinds):
        init_one = _init_mamba_layer(cfg, dtype)
        params["layers"] = jax.vmap(init_one)(layer_keys)
    elif cfg.is_mixed:
        kinds_arr = np.array(kinds)
        params["layers"] = {
            MAMBA: jax.vmap(_init_mamba_layer(cfg, dtype))(
                layer_keys[kinds_arr == MAMBA]),
            ATTN: jax.vmap(_init_attn_layer(cfg, dtype, with_cross=False))(
                layer_keys[kinds_arr == ATTN]),
        }
    else:
        raise ValueError(f"unsupported layer pattern: {cfg.name}")

    if cfg.shared_attn_every:
        # zamba2: ONE shared-weight attention+MLP block
        shared_cfg_key = keys[3]
        ka, km = jax.random.split(shared_cfg_key)
        params["shared_attn"] = {
            "ln1": jnp.zeros((cfg.d_model,), dtype),
            "attn": init_attn_params(ka, cfg, dtype),
            "ln2": jnp.zeros((cfg.d_model,), dtype),
            "mlp": _init_mlp(km, cfg.d_model, cfg.d_ff, dtype),
        }

    if cfg.is_encoder_decoder:
        enc_keys = jax.random.split(keys[4], cfg.enc_layers)
        enc_init = _init_attn_layer(cfg, dtype, with_cross=False)
        params["encoder"] = {
            "layers": jax.vmap(enc_init)(enc_keys),
            "norm": jnp.zeros((cfg.d_model,), dtype),
        }
    return params


# ---------------------------------------------------------------------------
# Per-layer metadata (windows / thetas) — static numpy, becomes scan xs
# ---------------------------------------------------------------------------
def layer_windows_thetas(cfg: ModelConfig) -> tuple[np.ndarray, np.ndarray]:
    n_attn = sum(1 for k in cfg.layer_kinds() if k == ATTN)
    wp = cfg.window_pattern
    windows = np.array([wp[i % len(wp)] for i in range(max(n_attn, 1))],
                       np.int32)
    windows = np.where(windows == GLOBAL, 0, windows)  # 0 => global
    theta_g = cfg.rope_theta_global or cfg.rope_theta
    thetas = np.where(windows == 0, theta_g, cfg.rope_theta).astype(np.float32)
    return windows, thetas


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def _embed_in(params, cfg: ModelConfig, tokens=None, embeds=None):
    adt = jnp.dtype(cfg.activation_dtype)
    if embeds is not None:
        x = embeds.astype(adt)
    else:
        x = params["embed"][tokens].astype(adt)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), adt)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, adt)
    return shard_act(x, ("batch", "seq", "embed"))


def _unembed(params, cfg: ModelConfig, x):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = jnp.einsum("bsd,dv->bsv", x, head).astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    logits = shard_act(logits, ("batch", "seq", "vocab"))
    if cfg.final_logit_softcap is not None:
        logits = softcap(logits, cfg.final_logit_softcap)
    return logits


# ---------------------------------------------------------------------------
# Attention-family layer body (train / prefill / decode), scan-compatible
# ---------------------------------------------------------------------------
def _attn_layer(cfg: ModelConfig, mode: str, segment_ids=None):
    """Returns body(x, xs) -> (x, ys). xs carries layer params + metadata +
    cache slices; ys carries updated cache slices + moe aux.
    ``segment_ids`` (full mode) keeps texts packed into a row apart."""

    def body(x, xs):
        lp = xs["layer"]
        window, theta = xs["window"], xs["theta"]
        ap = AttnParams(*lp["attn"]) if not isinstance(
            lp["attn"], AttnParams) else lp["attn"]

        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        ys = {}
        if mode == "full":
            a_out = attn_mod.attend_full(ap, h, cfg, window=window, theta=theta,
                                         segment_ids=segment_ids)
        elif mode == "prefill":
            a_out, k, v = attn_mod.prefill(
                ap, h, cfg, window=window, theta=theta,
                cache_len=xs["cache_len"])
            ys["k"], ys["v"] = k, v
        elif mode == "decode":
            a_out, k, v = attn_mod.decode_step(
                ap, h, xs["k"], xs["v"], xs["cache_pos"], cfg,
                window=window, theta=theta)
            ys["k"], ys["v"] = k, v
        else:
            raise ValueError(mode)
        if cfg.use_post_norm:
            a_out = rms_norm(a_out, lp["post_ln1"], cfg.norm_eps)
        x = x + a_out

        if cfg.is_encoder_decoder:
            cp = CrossAttnParams(*lp["cross"]) if not isinstance(
                lp["cross"], CrossAttnParams) else lp["cross"]
            hc = rms_norm(x, lp["ln_cross"], cfg.norm_eps)
            x = x + cross_attend(cp, hc, xs["cross_k"], xs["cross_v"], cfg)

        m_out, aux, _ = _ffn(cfg, lp, x)
        if cfg.use_post_norm:
            m_out = rms_norm(m_out, lp["post_ln2"], cfg.norm_eps)
        x = x + m_out
        ys["aux"] = aux
        return x, ys

    return body


def _ffn(cfg: ModelConfig, lp, x):
    """The MLP branch of a layer on ``norm(x)``: dense, dropping MoE or
    dropless MoE, plus the shared expert. Returns (branch, router aux
    loss, (token, expert) pairs handed to the held experts)."""
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    rows = jnp.zeros((), jnp.int32)
    with jax.named_scope("moe" if cfg.is_moe else "mlp"):
        if cfg.is_moe:
            mp = MoEParams(*lp["moe"]) if not isinstance(
                lp["moe"], MoEParams) else lp["moe"]
            if cfg.moe_capacity_factor:
                out, aux = moe_ffn(mp, h, cfg.num_experts,
                                   cfg.experts_per_token,
                                   cfg.router_aux_coef,
                                   cfg.moe_capacity_factor)
            else:
                out, aux, rows = moe_dropless(mp, h, cfg.experts_per_token,
                                              cfg.router_aux_coef)
        else:
            mlp = lp["mlp"]
            out = swiglu(h, mlp["w_gate"], mlp["w_up"], mlp["w_down"])
        if "shared" in lp:
            out = out + shared_expert(lp["shared"], h)
    return out, aux, rows


def _scan_attn_layers(params, cfg, x, mode, *, cache=None, cache_pos=None,
                      cache_len=None, cross=None, remat=False,
                      segment_ids=None):
    windows, thetas = layer_windows_thetas(cfg)
    xs = {
        "layer": params["layers"],
        "window": jnp.asarray(windows),
        "theta": jnp.asarray(thetas),
    }
    if mode == "decode":
        xs["k"], xs["v"] = cache["k"], cache["v"]
        xs["cache_pos"] = jnp.broadcast_to(
            jnp.asarray(cache_pos, jnp.int32), (cfg.num_layers,))
    if cross is not None:
        xs["cross_k"], xs["cross_v"] = cross

    body = _attn_layer(cfg, mode, segment_ids)
    if mode == "prefill":
        # cache_len is a *static* python int (defines cache shapes): closure
        body_inner = body

        def body(x, xs_):  # noqa: F811
            xs_ = dict(xs_)
            xs_["cache_len"] = cache_len
            return body_inner(x, xs_)

    if remat:
        body = jax.checkpoint(body)
    x, ys = jax.lax.scan(body, x, xs)
    new_cache = None
    if mode in ("prefill", "decode"):
        new_cache = {"k": ys["k"], "v": ys["v"]}
        if cross is not None:
            new_cache["cross_k"], new_cache["cross_v"] = cross
    return x, new_cache, jnp.sum(ys["aux"])


# ---------------------------------------------------------------------------
# Ring-cache decode for periodic local:global patterns (gemma2/3 — §Perf)
# ---------------------------------------------------------------------------
def _ring_split(cfg: ModelConfig):
    """(period, n_super, tail, local positions-in-period, global positions)."""
    p = len(cfg.window_pattern)
    n_super = cfg.num_layers // p
    tail = cfg.num_layers - n_super * p
    local_js = [j for j, w in enumerate(cfg.window_pattern) if w > 0]
    global_js = [j for j, w in enumerate(cfg.window_pattern) if w <= 0]
    return p, n_super, tail, local_js, global_js


def uses_ring_cache(cfg: ModelConfig) -> bool:
    return (cfg.ring_cache and not cfg.is_encoder_decoder
            and not cfg.shared_attn_every
            and all(k == ATTN for k in cfg.layer_kinds())
            and any(w > 0 for w in cfg.window_pattern)
            and len(cfg.window_pattern) <= cfg.num_layers)


def _mlp_and_residual(cfg, lp, x, a_out):
    if cfg.use_post_norm:
        a_out = rms_norm(a_out, lp["post_ln1"], cfg.norm_eps)
    x = x + a_out
    m_out, _, _ = _ffn(cfg, lp, x)
    if cfg.use_post_norm:
        m_out = rms_norm(m_out, lp["post_ln2"], cfg.norm_eps)
    return x + m_out


def _ring_layer(cfg, lp, x, kind_window, theta, k_cache, v_cache,
                cache_pos):
    """One unrolled decode layer; window > 0 -> ring cache semantics."""
    ap = AttnParams(*lp["attn"]) if not isinstance(
        lp["attn"], AttnParams) else lp["attn"]
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if kind_window > 0:
        a_out, k_cache, v_cache = attn_mod.ring_decode_step(
            ap, h, k_cache, v_cache, cache_pos, cfg,
            window=kind_window, theta=theta)
    else:
        a_out, k_cache, v_cache = attn_mod.decode_step(
            ap, h, k_cache, v_cache, cache_pos, cfg,
            window=jnp.asarray(0, jnp.int32), theta=theta)
    x = _mlp_and_residual(cfg, lp, x, a_out)
    return x, k_cache, v_cache


def _scan_ring_decode(params, cfg, x, cache, cache_pos):
    """Decode scan over super-blocks with per-position static windows:
    local layers carry (n_super, n_loc, B, W, KV, hd) ring caches, global
    layers full-length caches. Tail layers (L % period) unroll outside."""
    p, n_super, tail, local_js, global_js = _ring_split(cfg)
    windows, thetas = layer_windows_thetas(cfg)

    def slice_fold(tree, start, count, fold):
        out = jax.tree.map(lambda a: a[start:start + count], tree)
        if fold:
            out = jax.tree.map(
                lambda a: a.reshape((n_super, p) + a.shape[1:]), out)
        return out

    super_params = slice_fold(params["layers"], 0, n_super * p, True)
    loc_of_j = {j: i for i, j in enumerate(local_js)}
    glob_of_j = {j: i for i, j in enumerate(global_js)}

    def super_body(x, xs):
        ring_k, ring_v = xs["ring_k"], xs["ring_v"]  # (n_loc, B, W, KV, hd)
        glob_k, glob_v = xs["glob_k"], xs["glob_v"]  # (n_glob, B, S, KV, hd)
        for j in range(p):  # static unroll over the period
            lp = jax.tree.map(lambda a: a[j], xs["params"])
            w = int(windows[j])
            th = jnp.asarray(float(thetas[j]), jnp.float32)
            if w > 0:
                i = loc_of_j[j]
                x, nk, nv = _ring_layer(cfg, lp, x, w, th, ring_k[i],
                                        ring_v[i], cache_pos)
                ring_k = ring_k.at[i].set(nk)
                ring_v = ring_v.at[i].set(nv)
            else:
                i = glob_of_j[j]
                x, nk, nv = _ring_layer(cfg, lp, x, 0, th, glob_k[i],
                                        glob_v[i], cache_pos)
                glob_k = glob_k.at[i].set(nk)
                glob_v = glob_v.at[i].set(nv)
        return x, {"ring_k": ring_k, "ring_v": ring_v,
                   "glob_k": glob_k, "glob_v": glob_v}

    xs = {"params": super_params,
          "ring_k": cache["ring_k"], "ring_v": cache["ring_v"],
          "glob_k": cache["glob_k"], "glob_v": cache["glob_v"]}
    x, ys = jax.lax.scan(super_body, x, xs)
    new_cache = {k: ys[k] for k in ("ring_k", "ring_v", "glob_k", "glob_v")}

    if tail:
        tail_params = slice_fold(params["layers"], n_super * p, tail, False)
        tk, tv = cache["tail_k"], cache["tail_v"]
        for t in range(tail):
            j = (n_super * p + t) % p
            lp = jax.tree.map(lambda a: a[t], tail_params)
            w = int(windows[n_super * p + t])
            th = jnp.asarray(float(thetas[n_super * p + t]), jnp.float32)
            x, nk, nv = _ring_layer(cfg, lp, x, w, th, tk[t], tv[t],
                                    cache_pos)
            tk = tk.at[t].set(nk)
            tv = tv.at[t].set(nv)
        new_cache["tail_k"], new_cache["tail_v"] = tk, tv
    return x, new_cache, jnp.zeros((), jnp.float32)


def init_ring_cache(cfg: ModelConfig, batch: int, max_seq: int,
                    dtype=None) -> dict:
    """Ring-structured decode cache (see _scan_ring_decode)."""
    dtype = dtype or jnp.dtype(cfg.activation_dtype)
    p, n_super, tail, local_js, global_js = _ring_split(cfg)
    windows, _ = layer_windows_thetas(cfg)
    w_max = max(int(w) for w in windows if w > 0)
    kvh = (cfg.num_kv_heads, cfg.head_dim)
    cache = {
        "ring_k": jnp.zeros((n_super, len(local_js), batch, w_max) + kvh,
                            dtype),
        "ring_v": jnp.zeros((n_super, len(local_js), batch, w_max) + kvh,
                            dtype),
        "glob_k": jnp.zeros((n_super, len(global_js), batch, max_seq) + kvh,
                            dtype),
        "glob_v": jnp.zeros((n_super, len(global_js), batch, max_seq) + kvh,
                            dtype),
    }
    if tail:
        tail_ws = [int(windows[n_super * p + t]) for t in range(tail)]
        t_len = max(w if w > 0 else max_seq for w in tail_ws)
        cache["tail_k"] = jnp.zeros((tail, batch, t_len) + kvh, dtype)
        cache["tail_v"] = jnp.zeros((tail, batch, t_len) + kvh, dtype)
    return cache


def ring_cache_from_full(cfg: ModelConfig, cache: dict, cache_pos: int,
                         batch: int, max_seq: int) -> dict:
    """Convert a standard prefill cache into the ring structure (serving
    pipeline: prefill full, then decode with ring caches)."""
    p, n_super, tail, local_js, global_js = _ring_split(cfg)
    windows, _ = layer_windows_thetas(cfg)
    ring = init_ring_cache(cfg, batch, max_seq, cache["k"].dtype)
    w_max = ring["ring_k"].shape[3]

    def gather_window(full_layer, w):
        # place true positions (pos-w, pos] at slot true_pos % w_max
        slots = jnp.arange(w_max)
        # fill such that slot s holds position q where q % w_max == s
        base = jnp.maximum(cache_pos - w_max, -w_max)
        cand = ((cache_pos // w_max) * w_max) + slots
        q = jnp.where(cand <= cache_pos, cand, cand - w_max)
        q_clamped = jnp.clip(q, 0, max_seq - 1)
        out = full_layer[:, q_clamped]
        valid = (q >= 0) & (q > cache_pos - w_max)
        return out * valid[None, :, None, None].astype(out.dtype)

    for idx in range(cfg.num_layers):
        s, j = divmod(idx, p)
        is_tail = s >= n_super
        k_l, v_l = cache["k"][idx], cache["v"][idx]
        if is_tail:
            t = idx - n_super * p
            if int(windows[idx]) > 0:
                ring["tail_k"] = ring["tail_k"].at[t].set(
                    gather_window(k_l, int(windows[idx])))
                ring["tail_v"] = ring["tail_v"].at[t].set(
                    gather_window(v_l, int(windows[idx])))
            else:
                ring["tail_k"] = ring["tail_k"].at[t].set(k_l)
                ring["tail_v"] = ring["tail_v"].at[t].set(v_l)
            continue
        if int(windows[idx]) > 0:
            i = local_js.index(j)
            ring["ring_k"] = ring["ring_k"].at[s, i].set(
                gather_window(k_l, int(windows[idx])))
            ring["ring_v"] = ring["ring_v"].at[s, i].set(
                gather_window(v_l, int(windows[idx])))
        else:
            i = global_js.index(j)
            ring["glob_k"] = ring["glob_k"].at[s, i].set(k_l)
            ring["glob_v"] = ring["glob_v"].at[s, i].set(v_l)
    return ring


# ---------------------------------------------------------------------------
# Mamba-family (pure SSM) scan
# ---------------------------------------------------------------------------
def _scan_mamba_layers(params, cfg, x, mode, *, cache=None, remat=False,
                       segment_ids=None):
    decode = mode == "decode"

    def body(x, xs):
        lp = xs["layer"]
        mp = MambaParams(*lp["mamba"]) if not isinstance(
            lp["mamba"], MambaParams) else lp["mamba"]
        h = rms_norm(x, lp["ln"], cfg.norm_eps)
        out, (ssm_s, conv_s) = mamba_block(
            mp, h, cfg,
            ssm_state=xs.get("ssm"), conv_state=xs.get("conv"),
            decode=decode, segment_ids=segment_ids)
        return x + out, {"ssm": ssm_s, "conv": conv_s}

    xs = {"layer": params["layers"]}
    if decode:
        xs["ssm"], xs["conv"] = cache["ssm"], cache["conv"]
    if remat:
        body = jax.checkpoint(body)
    x, ys = jax.lax.scan(body, x, xs)
    new_cache = {"ssm": ys["ssm"], "conv": ys["conv"]}
    return x, new_cache, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Hybrid (zamba2): super-blocks of mamba + shared attention
# ---------------------------------------------------------------------------
def _zamba_split(cfg) -> tuple[int, int, int]:
    k = cfg.shared_attn_every
    n_super = cfg.num_layers // k
    tail = cfg.num_layers - n_super * k
    return k, n_super, tail


def _shared_attn_apply(params, cfg, x, mode, k_cache=None, v_cache=None,
                       cache_pos=None, cache_len=None):
    sp = params["shared_attn"]
    ap = AttnParams(*sp["attn"]) if not isinstance(
        sp["attn"], AttnParams) else sp["attn"]
    h = rms_norm(x, sp["ln1"], cfg.norm_eps)
    window = jnp.asarray(0, jnp.int32)  # global
    theta = jnp.asarray(cfg.rope_theta, jnp.float32)
    ys = {}
    if mode == "full":
        a_out = attn_mod.attend_full(ap, h, cfg, window=window, theta=theta)
    elif mode == "prefill":
        a_out, k, v = attn_mod.prefill(ap, h, cfg, window=window, theta=theta,
                                       cache_len=cache_len)
        ys["k"], ys["v"] = k, v
    else:
        a_out, k, v = attn_mod.decode_step(
            ap, h, k_cache, v_cache, cache_pos, cfg, window=window,
            theta=theta)
        ys["k"], ys["v"] = k, v
    x = x + a_out
    h2 = rms_norm(x, sp["ln2"], cfg.norm_eps)
    mlp = sp["mlp"]
    x = x + swiglu(h2, mlp["w_gate"], mlp["w_up"], mlp["w_down"])
    return x, ys


def _run_hybrid(params, cfg, x, mode, *, cache=None, cache_pos=None,
                cache_len=None, remat=False):
    k, n_super, tail = _zamba_split(cfg)
    decode = mode == "decode"
    trunk = params["layers"]

    def slice_layers(tree, start, count, fold):
        """Take layers [start, start+count) and optionally fold into
        (n_super, k, ...)."""
        out = jax.tree.map(lambda a: a[start: start + count], tree)
        if fold:
            out = jax.tree.map(
                lambda a: a.reshape((n_super, k) + a.shape[1:]), out)
        return out

    super_trunk = slice_layers(trunk, 0, n_super * k, fold=True)
    tail_trunk = slice_layers(trunk, n_super * k, tail, fold=False) \
        if tail else None

    def mamba_body(x, xs):
        lp = xs["layer"]
        mp = MambaParams(*lp["mamba"]) if not isinstance(
            lp["mamba"], MambaParams) else lp["mamba"]
        h = rms_norm(x, lp["ln"], cfg.norm_eps)
        out, (ssm_s, conv_s) = mamba_block(
            mp, h, cfg, ssm_state=xs.get("ssm"), conv_state=xs.get("conv"),
            decode=decode)
        return x + out, {"ssm": ssm_s, "conv": conv_s}

    if remat:
        mamba_body = jax.checkpoint(mamba_body)

    def super_body(x, xs):
        inner_xs = {"layer": xs["trunk"]}
        if decode:
            inner_xs["ssm"], inner_xs["conv"] = xs["ssm"], xs["conv"]
        x, inner_ys = jax.lax.scan(mamba_body, x, inner_xs)
        x, shared_ys = _shared_attn_apply(
            params, cfg, x, mode,
            k_cache=xs.get("shared_k"), v_cache=xs.get("shared_v"),
            cache_pos=cache_pos, cache_len=cache_len)
        ys = {"ssm": inner_ys["ssm"], "conv": inner_ys["conv"], **shared_ys}
        return x, ys

    xs = {"trunk": super_trunk}
    if decode:
        fold = lambda a: a.reshape((n_super, k) + a.shape[1:])  # noqa: E731
        xs["ssm"] = fold(cache["ssm"][: n_super * k])
        xs["conv"] = fold(cache["conv"][: n_super * k])
        xs["shared_k"], xs["shared_v"] = cache["shared_k"], cache["shared_v"]

    x, ys = jax.lax.scan(super_body, x, xs)

    new_cache = {}
    unfold = lambda a: a.reshape((n_super * k,) + a.shape[2:])  # noqa: E731
    ssm_parts = [unfold(ys["ssm"])]
    conv_parts = [unfold(ys["conv"])]
    if mode in ("prefill", "decode"):
        new_cache["shared_k"], new_cache["shared_v"] = ys["k"], ys["v"]

    if tail:
        tail_xs = {"layer": tail_trunk}
        if decode:
            tail_xs["ssm"] = cache["ssm"][n_super * k:]
            tail_xs["conv"] = cache["conv"][n_super * k:]
        x, tail_ys = jax.lax.scan(mamba_body, x, tail_xs)
        ssm_parts.append(tail_ys["ssm"])
        conv_parts.append(tail_ys["conv"])

    new_cache["ssm"] = jnp.concatenate(ssm_parts, axis=0)
    new_cache["conv"] = jnp.concatenate(conv_parts, axis=0)
    return x, new_cache, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Mixed (granite-4.0-h): Mamba2 and attention layers with their own weights,
# each followed by its MLP/MoE
# ---------------------------------------------------------------------------
def _mixed_runs(cfg: ModelConfig):
    """The period's runs of same-kind layers: (kind, first index among
    the period's layers of that kind, length)."""
    runs, seen = [], {MAMBA: 0, ATTN: 0}
    for kind in cfg.block_pattern:
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen[kind], 1])
        seen[kind] += 1
    return [tuple(r) for r in runs]


def _mixed_layer(cfg: ModelConfig, kind: str, mode: str, cache_pos=None,
                 cache_len=None, segment_ids=None):
    """body(x, xs) -> (x, ys) of one layer: ``x + r * mixer(norm(x))``,
    then ``x + r * (moe(norm(x)) + shared(norm(x)))``, ``r`` the residual
    multiplier. ``xs`` holds the layer's params (``p``) and, in decode,
    its cache; ``ys`` its new cache, router aux loss and expert rows.
    ``segment_ids`` (full mode) keeps texts packed into a row apart in
    the mixers; the MoE, norms and shared expert act per token."""
    r = cfg.residual_multiplier

    def body(x, xs):
        lp, ys = xs["p"], {}
        if kind == MAMBA:
            mp = MambaParams(*lp["mamba"]) if not isinstance(
                lp["mamba"], MambaParams) else lp["mamba"]
            h = rms_norm(x, lp["ln"], cfg.norm_eps)
            with jax.named_scope("mamba"):
                out, (ys["ssm"], ys["conv"]) = mamba_block(
                    mp, h, cfg, ssm_state=xs.get("ssm"),
                    conv_state=xs.get("conv"), decode=mode == "decode",
                    segment_ids=segment_ids)
        else:
            ap = AttnParams(*lp["attn"]) if not isinstance(
                lp["attn"], AttnParams) else lp["attn"]
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            window = jnp.asarray(0, jnp.int32)  # global
            theta = jnp.asarray(cfg.rope_theta, jnp.float32)
            with jax.named_scope("attn"):
                if mode == "full":
                    out = attn_mod.attend_full(ap, h, cfg, window=window,
                                               theta=theta,
                                               segment_ids=segment_ids)
                elif mode == "prefill":
                    out, ys["k"], ys["v"] = attn_mod.prefill(
                        ap, h, cfg, window=window, theta=theta,
                        cache_len=cache_len)
                else:
                    out, ys["k"], ys["v"] = attn_mod.decode_step(
                        ap, h, xs["k"], xs["v"], cache_pos, cfg,
                        window=window, theta=theta)
        x = x + r * out
        m_out, ys["aux"], ys["rows"] = _ffn(cfg, lp, x)
        return x + r * m_out, ys

    return body


def _run_mixed(params, cfg, x, mode, *, cache=None, cache_pos=None,
               cache_len=None, remat=False, segment_ids=None):
    """Scan over whole periods; inside one, a scan per run of same-kind
    layers. Returns (x, cache, aux, expert rows)."""
    period = cfg.block_pattern
    n_per = cfg.num_layers // len(period)
    count = {k: period.count(k) for k in (MAMBA, ATTN)}
    state = {MAMBA: ("ssm", "conv"), ATTN: ("k", "v")}

    def fold(kind, a):
        return a.reshape((n_per, count[kind]) + a.shape[1:])

    xs = {kind: {"p": jax.tree.map(functools.partial(fold, kind),
                                   params["layers"][kind])}
          for kind in (MAMBA, ATTN)}
    if mode == "decode":
        for kind in (MAMBA, ATTN):
            for name in state[kind]:
                xs[kind][name] = fold(kind, cache[name])
    bodies = {}
    for kind in (MAMBA, ATTN):
        body = _mixed_layer(cfg, kind, mode, cache_pos, cache_len,
                            segment_ids)
        bodies[kind] = jax.checkpoint(body) if remat else body

    def period_body(x, pxs):
        outs = {MAMBA: [], ATTN: []}
        for kind, start, n in _mixed_runs(cfg):
            run_xs = jax.tree.map(lambda a: a[start:start + n], pxs[kind])
            if n == 1:
                x, ys = bodies[kind](
                    x, jax.tree.map(lambda a: a[0], run_xs))
                ys = jax.tree.map(lambda a: a[None], ys)
            else:
                x, ys = jax.lax.scan(bodies[kind], x, run_xs)
            outs[kind].append(ys)
        ys = {kind: jax.tree.map(lambda *a: jnp.concatenate(a), *outs[kind])
              for kind in (MAMBA, ATTN)}
        return x, ys

    x, ys = jax.lax.scan(period_body, x, xs)
    aux = sum(jnp.sum(ys[k]["aux"]) for k in (MAMBA, ATTN))
    rows = sum(jnp.sum(ys[k]["rows"]) for k in (MAMBA, ATTN))
    new_cache = None
    if mode in ("prefill", "decode"):
        unfold = lambda a: a.reshape((-1,) + a.shape[2:])  # noqa: E731
        new_cache = {name: unfold(ys[kind][name])
                     for kind in (MAMBA, ATTN) for name in state[kind]}
    return x, new_cache, aux, rows


# ---------------------------------------------------------------------------
# Encoder (whisper)
# ---------------------------------------------------------------------------
def encode(params, cfg: ModelConfig, enc_embeds):
    """Bidirectional encoder over stub frame embeddings (B, S_enc, d)."""
    x = enc_embeds.astype(jnp.dtype(cfg.activation_dtype))

    def body(x, xs):
        lp = xs["layer"]
        ap = AttnParams(*lp["attn"]) if not isinstance(
            lp["attn"], AttnParams) else lp["attn"]
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        x = x + encoder_self_attend(ap, h, cfg)
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        mlp = lp["mlp"]
        x = x + swiglu(h2, mlp["w_gate"], mlp["w_up"], mlp["w_down"])
        return x, None

    x, _ = jax.lax.scan(body, x, {"layer": params["encoder"]["layers"]})
    return rms_norm(x, params["encoder"]["norm"], cfg.norm_eps)


def _stacked_cross_kv(params, cfg, enc_out):
    """Per-decoder-layer cross KV, stacked (L, B, S_enc, KV, hd)."""

    def one(layer):
        cp = CrossAttnParams(*layer["cross"]) if not isinstance(
            layer["cross"], CrossAttnParams) else layer["cross"]
        return cross_kv(cp, enc_out, cfg)

    ks, vs = jax.vmap(one, in_axes=(0,))(params["layers"])
    return ks, vs


# ---------------------------------------------------------------------------
# Cache allocation
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=None) -> PyTree:
    """Pre-allocated decode cache for every family."""
    dtype = dtype or jnp.dtype(cfg.activation_dtype)
    kinds = cfg.layer_kinds()
    cache: dict = {}
    if uses_ring_cache(cfg):
        return init_ring_cache(cfg, batch, max_seq, dtype)
    if cfg.is_mixed:
        d_in, n_heads, conv_dim = mamba_dims(cfg)
        n_mamba = sum(1 for kk in kinds if kk == MAMBA)
        n_attn = cfg.num_layers - n_mamba
        cache["ssm"] = jnp.zeros(
            (n_mamba, batch, n_heads, cfg.ssm_head_dim, cfg.ssm_state_size),
            jnp.float32)
        cache["conv"] = jnp.zeros(
            (n_mamba, batch, cfg.ssm_conv_width - 1, conv_dim), dtype)
        cache["k"] = jnp.zeros(
            (n_attn, batch, max_seq, cfg.num_kv_heads, cfg.head_dim), dtype)
        cache["v"] = jnp.zeros_like(cache["k"])
    elif cfg.shared_attn_every:  # hybrid
        d_in, n_heads, conv_dim = mamba_dims(cfg)
        k, n_super, tail = _zamba_split(cfg)
        cache["ssm"] = jnp.zeros(
            (cfg.num_layers, batch, n_heads, cfg.ssm_head_dim,
             cfg.ssm_state_size), jnp.float32)
        cache["conv"] = jnp.zeros(
            (cfg.num_layers, batch, cfg.ssm_conv_width - 1, conv_dim), dtype)
        cache["shared_k"] = jnp.zeros(
            (n_super, batch, max_seq, cfg.num_kv_heads, cfg.head_dim), dtype)
        cache["shared_v"] = jnp.zeros_like(cache["shared_k"])
    elif all(kk == MAMBA for kk in kinds):
        d_in, n_heads, conv_dim = mamba_dims(cfg)
        cache["ssm"] = jnp.zeros(
            (cfg.num_layers, batch, n_heads, cfg.ssm_head_dim,
             cfg.ssm_state_size), jnp.float32)
        cache["conv"] = jnp.zeros(
            (cfg.num_layers, batch, cfg.ssm_conv_width - 1, conv_dim), dtype)
    else:
        cache["k"] = jnp.zeros(
            (cfg.num_layers, batch, max_seq, cfg.num_kv_heads, cfg.head_dim),
            dtype)
        cache["v"] = jnp.zeros_like(cache["k"])
        if cfg.is_encoder_decoder:
            cache["cross_k"] = jnp.zeros(
                (cfg.num_layers, batch, cfg.enc_seq_len, cfg.num_kv_heads,
                 cfg.head_dim), dtype)
            cache["cross_v"] = jnp.zeros_like(cache["cross_k"])
    return cache


# ---------------------------------------------------------------------------
# Public forward
# ---------------------------------------------------------------------------
def has_segment_path(cfg: ModelConfig) -> bool:
    """Whether ``hidden_states`` takes ``segment_ids``, to keep texts
    packed into one row apart: the mixed family and plain Mamba-2 or
    decoder-only attention stacks; not the encoder-decoder nor the
    shared-attention hybrid."""
    return cfg.is_mixed or (not cfg.is_encoder_decoder
                            and not cfg.shared_attn_every
                            and len(set(cfg.layer_kinds())) == 1)


def _trunk(params, cfg: ModelConfig, x, mode: str, *, cache=None,
           cache_pos=None, cache_len=None, cross=None, remat=False,
           segment_ids=None):
    """Every layer, for each family. Returns (x, cache, aux, expert
    rows handed to the dropless MoE's grouped products). ``segment_ids``
    (full mode, families of ``has_segment_path``) keeps packed texts
    apart."""
    no_rows = jnp.zeros((), jnp.int32)
    if segment_ids is not None and (mode != "full"
                                    or not has_segment_path(cfg)):
        raise ValueError(f"{cfg.name}: segment ids need the full-sequence "
                         "path of a family with a segment path")
    if mode == "decode" and cache is not None and "ring_k" in cache:
        return (*_scan_ring_decode(params, cfg, x, cache, cache_pos),
                no_rows)
    if cfg.is_mixed:
        return _run_mixed(params, cfg, x, mode, cache=cache,
                          cache_pos=cache_pos, cache_len=cache_len,
                          remat=remat, segment_ids=segment_ids)
    if cfg.shared_attn_every:
        return (*_run_hybrid(params, cfg, x, mode, cache=cache,
                             cache_pos=cache_pos, cache_len=cache_len,
                             remat=remat), no_rows)
    if all(k == MAMBA for k in cfg.layer_kinds()):
        # mamba "prefill" == full forward; final states are the cache
        return (*_scan_mamba_layers(
            params, cfg, x, mode if mode != "prefill" else "full",
            cache=cache, remat=remat, segment_ids=segment_ids), no_rows)
    return (*_scan_attn_layers(params, cfg, x, mode, cache=cache,
                               cache_pos=cache_pos, cache_len=cache_len,
                               cross=cross, remat=remat,
                               segment_ids=segment_ids), no_rows)


def hidden_states(params, cfg: ModelConfig, tokens=None, embeds=None,
                  enc_embeds=None, remat: bool = False, segment_ids=None):
    """Final-layer hidden states (pre-unembed) — the frozen-backbone
    embedding interface used by the preference pipeline. Returns
    (hidden (B, S, d), router aux loss, expert rows).

    ``segment_ids`` (B, S) packs several texts into a row: each id's
    tokens are contiguous, and every text is computed as if alone, from
    position 0 (``has_segment_path`` names the families that take it).
    None is one text a row."""
    x = _embed_in(params, cfg, tokens=tokens, embeds=embeds)
    cross = None
    if cfg.is_encoder_decoder:
        enc_out = encode(params, cfg, enc_embeds)
        cross = _stacked_cross_kv(params, cfg, enc_out)
    x, _, aux, rows = _trunk(params, cfg, x, "full", cross=cross,
                             remat=remat, segment_ids=segment_ids)
    return rms_norm(x, params["final_norm"], cfg.norm_eps), aux, rows


def forward(params, cfg: ModelConfig, *, tokens=None, embeds=None,
            enc_embeds=None, cache=None, cache_pos=None,
            prefill_len: Optional[int] = None, remat: bool = False):
    """Unified forward.

    Modes:
      * train/full  : cache=None, prefill_len=None -> (logits, None, aux)
      * prefill     : prefill_len=S_max            -> (logits, cache, aux)
      * decode      : cache + cache_pos, S==1      -> (logits, cache, aux)
    """
    x = _embed_in(params, cfg, tokens=tokens, embeds=embeds)

    mode = "full"
    if prefill_len is not None:
        mode = "prefill"
    elif cache is not None:
        mode = "decode"

    cross = None
    if cfg.is_encoder_decoder:
        if mode == "decode":
            cross = (cache["cross_k"], cache["cross_v"])
        else:
            enc_out = encode(params, cfg, enc_embeds)
            cross = _stacked_cross_kv(params, cfg, enc_out)

    x, new_cache, aux, _ = _trunk(params, cfg, x, mode, cache=cache,
                                  cache_pos=cache_pos, cache_len=prefill_len,
                                  cross=cross, remat=remat)
    logits = _unembed(params, cfg, x)
    return logits, new_cache, aux
