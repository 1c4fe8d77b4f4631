"""Plain reference of the granite-4.0-h backbone (GraniteMoeHybrid,
hf:ibm-granite/granite-4.0-h-small) as a frozen embedder: hidden states
of one token text, and their masked mean pool.

Written out in ``jax.numpy`` in float32, every product at ``highest``
precision, with no chunking, cache or batching: one text, one layer at a
time. It imports nothing of the program. A layer is

    x = x + r * mixer(norm(x))
    x = x + r * (moe(norm(x)) + shared(norm(x)))

with ``r`` the residual multiplier, the mixer a Mamba-2 or an attention
layer as ``layer_types`` says, after ``embed[tokens] * embedding
multiplier``, and a final norm. Mamba-2 is its direct masked (quadratic)
form over the whole sequence,

    y_t = sum_{s<=t} (C_t . B_s) exp(sum_{r=s+1..t} dt_r A) dt_s x_s + D x_t

after a causal depthwise conv with bias and SiLU, then gated by SiLU(z),
RMS-normed over the inner width (one group) and projected out.
Attention is causal GQA with no positional encoding, scores scaled by
the attention multiplier. The router takes the top ``k`` of its logits
over every expert, gated by a softmax over those ``k``; the experts (a
SwiGLU each) are computed densely over the ones held, weighted by their
gates, and the shared expert is added.

Departures from the published description:

* norm scales are stored as ``s`` and applied as ``1 + s`` (the model
  stores the product ``1 + s`` itself);
* only the experts whose weights are given (the first ``E_held``) are
  computed: what experts held elsewhere would add is left out, as the
  program leaves it out (the chip's share of a layer);
* weights are whatever dtype they come in, computed in float32;
* a text may carry padding past its ``length``: every layer is causal,
  so the padding changes no earlier position, and the pool leaves it
  out. The pool (masked mean of the final-normed hidden states) is the
  embedder's, not part of the published model.

``control`` (a dtype below the configuration's, e.g. float8_e4m3fn)
rounds every weight and every product's operands to it: the same
computation one precision down.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32

MAMBA_WEIGHTS = ("ln", "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                 "norm", "out_proj")
ATTENTION_WEIGHTS = ("ln", "wq", "wk", "wv", "wo")
FFN_WEIGHTS = ("ln2", "router", "w_gate", "w_up", "w_down", "shared_gate",
               "shared_up", "shared_down")


def _round(a, control):
    a = a.astype(F32)
    return a if control is None else a.astype(control).astype(F32)


def _mm(spec, a, b, control):
    return jnp.einsum(spec, _round(a, control), _round(b, control),
                      precision=HIGHEST)


def _norm(x, s, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + s)


def _mamba(w, cfg, h, control):
    """The Mamba-2 mixer of (S, d) in the direct quadratic form."""
    s = h.shape[0]
    n_h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    d_in = n_h * p
    zxbcdt = _mm("sd,de->se", h, w["in_proj"], control)
    z = zxbcdt[:, :d_in]
    xbc = zxbcdt[:, d_in:2 * d_in + 2 * n]
    dt = jax.nn.softplus(zxbcdt[:, 2 * d_in + 2 * n:] + w["dt_bias"])  # (S, H)
    width = w["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, xbc.shape[1]), F32), xbc])
    conv_w = _round(w["conv_w"], control)
    xbc = sum(padded[i:i + s] * conv_w[i] for i in range(width)) + w["conv_b"]
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :d_in].reshape(s, n_h, p)
    b_, c_ = xbc[:, d_in:d_in + n], xbc[:, d_in + n:]
    a = -jnp.exp(w["A_log"])  # (H,)
    cum = jnp.cumsum(dt * a, axis=0)  # (S, H): sum_{r<=t} dt_r A
    t_idx = jnp.arange(s)
    causal = t_idx[:, None] >= t_idx[None, :]  # (t, s)
    decay = jnp.exp(jnp.where(causal[..., None],
                              cum[:, None, :] - cum[None, :, :], -jnp.inf))
    cb = _mm("tn,sn->ts", c_, b_, control)
    weights = cb[..., None] * decay * dt[None, :, :]  # (t, s, H)
    y = jnp.einsum("tsh,shp->thp", _round(weights, control),
                   _round(x, control), precision=HIGHEST)
    y = y + w["D"][None, :, None] * x
    y = y.reshape(s, d_in) * jax.nn.silu(z)
    y = _norm(y, w["norm"], cfg["rms_norm_eps"])
    return _mm("se,ed->sd", y, w["out_proj"], control)


def _attention(w, cfg, h, control):
    """Causal GQA with no positional encoding over (S, d)."""
    s = h.shape[0]
    n_q, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = w["wq"].shape[1] // n_q
    q = _mm("sd,de->se", h, w["wq"], control).reshape(s, n_q, hd)
    k = _mm("sd,de->se", h, w["wk"], control).reshape(s, n_kv, hd)
    v = _mm("sd,de->se", h, w["wv"], control).reshape(s, n_kv, hd)
    k = jnp.repeat(k, n_q // n_kv, axis=1)
    v = jnp.repeat(v, n_q // n_kv, axis=1)
    scores = _mm("thd,shd->hts", q, k, control) * cfg["attention_multiplier"]
    t_idx = jnp.arange(s)
    scores = jnp.where(t_idx[:, None] >= t_idx[None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _mm("hts,shd->thd", probs, v, control).reshape(s, n_q * hd)
    return _mm("se,ed->sd", out, w["wo"], control)


def _swiglu(h, gate, up, down, control):
    g = _mm("sd,df->sf", h, gate, control)
    u = _mm("sd,df->sf", h, up, control)
    return _mm("sf,fd->sd", jax.nn.silu(g) * u, down, control)


def _moe(w, cfg, h, control):
    """Top-k routing over every expert; the held experts, densely."""
    k = cfg["num_experts_per_tok"]
    logits = _mm("sd,de->se", h, w["router"], control)
    top, idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top, axis=-1)  # (S, k)
    held = w["w_gate"].shape[0]
    # gate of each held expert for each token (0 where not chosen)
    weight = jnp.sum(jax.nn.one_hot(idx, held, dtype=F32) * gates[..., None],
                     axis=1)  # (S, E_held)
    g = _mm("sd,edf->sef", h, w["w_gate"], control)
    u = _mm("sd,edf->sef", h, w["w_up"], control)
    act = jax.nn.silu(g) * u * weight[..., None]
    return _mm("sef,efd->sd", act, w["w_down"], control)


@functools.partial(jax.jit, static_argnames=("kind", "cfg_items", "control"))
def _layer(w, x, kind, cfg_items, control):
    cfg = dict(cfg_items)
    r, eps = cfg["residual_multiplier"], cfg["rms_norm_eps"]
    w = {name: _round(a, control) for name, a in w.items()}
    mixer = _mamba if kind == "mamba" else _attention
    x = x + r * mixer(w, cfg, _norm(x, w["ln"], eps), control)
    h = _norm(x, w["ln2"], eps)
    ffn = _moe(w, cfg, h, control) + _swiglu(
        h, w["shared_gate"], w["shared_up"], w["shared_down"], control)
    out = x + r * ffn
    return out if control is None else _round(out, control)


@functools.partial(jax.jit, static_argnames=("multiplier", "control"))
def _embed(table, tokens, multiplier, control):
    return _round(table[tokens], control) * multiplier


@functools.partial(jax.jit, static_argnames=("eps",))
def _final(x, s, length, eps):
    h = _norm(x, s.astype(F32), eps)
    mask = (jnp.arange(x.shape[0]) < length).astype(F32)
    return h, jnp.sum(h * mask[:, None], axis=0) / length


def hidden_states(weights: dict, cfg: dict, tokens, length=None,
                  control=None):
    """Final-normed hidden states (S, d) of one text ``tokens`` (S,),
    and their mean over the first ``length`` positions (all by default).

    ``weights``: ``embed`` (V, d), ``final_norm`` (d,), and ``layers``, one
    dict per entry of ``cfg["layer_types"]`` ("mamba" or "attention")
    holding that kind's weights (``MAMBA_WEIGHTS`` or
    ``ATTENTION_WEIGHTS``) and ``FFN_WEIGHTS``. ``cfg`` holds the
    published keys the layers read (``layer_types``, ``mamba_n_heads``,
    ``mamba_d_head``, ``mamba_d_state``, ``num_attention_heads``,
    ``num_key_value_heads``, ``attention_multiplier``,
    ``num_experts_per_tok``, ``embedding_multiplier``,
    ``residual_multiplier``, ``rms_norm_eps``)."""
    keys = ("mamba_n_heads", "mamba_d_head", "mamba_d_state",
            "num_attention_heads", "num_key_value_heads",
            "attention_multiplier", "num_experts_per_tok",
            "residual_multiplier", "rms_norm_eps")
    items = tuple((k, cfg[k]) for k in keys)
    x = _embed(weights["embed"], jnp.asarray(tokens),
               float(cfg["embedding_multiplier"]), control)
    for kind, w in zip(cfg["layer_types"], weights["layers"]):
        x = _layer(w, x, kind, items, control)
    length = x.shape[0] if length is None else length
    return _final(x, weights["final_norm"], length,
                  float(cfg["rms_norm_eps"]))
