"""GPO: the transformer-based group preference predictor (Zhao et al. 2023),
the module PluralLLM trains federatedly.

A transformer neural process (TNP-style):

* every (embedding x, preference y) pair becomes one token [x ; y ; is_ctx];
  target tokens carry y = 0 and is_ctx = 0;
* NO positional encoding — the predictor must be permutation-invariant in
  the context set (property-tested in tests/test_property.py);
* the neural-process mask: context tokens attend to context tokens;
  target tokens attend to context tokens and themselves, never to other
  targets (no information leaks between targets — Eq. 1's conditional
  independence);
* the head reads target tokens and emits the predicted preference
  (Gaussian mean; optional learned sigma), trained with Eq. 1's NLL,
  which for fixed sigma is MSE — GPO's practice.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import GPOConfig
from repro.kernels.quant_matmul import QuantizedLinear
from repro.models.layers import dense_init, rms_norm

NEG_INF = -1e30
# named scope of the attention in every forward (dense einsums and the
# Pallas kernel alike), so a profile finds it by one name
ATTENTION_SCOPE = "gpo_attention"


def _mm(x, w):
    """Dense-layer matmul with static weight-format dispatch: plain f32
    arrays multiply directly; ``QuantizedLinear`` leaves (the serving
    engine's load-time int8 weights, DESIGN.md §12) route through the
    fused int8 kernel. The pytree structure is static under jit, so the
    training path traces exactly as before."""
    if isinstance(w, QuantizedLinear):
        from repro.kernels import int8_matmul

        return int8_matmul(x, w.q, w.scale)
    return x @ w


class GPOLayer(NamedTuple):
    ln1: jnp.ndarray
    wq: jnp.ndarray
    wk: jnp.ndarray
    wv: jnp.ndarray
    wo: jnp.ndarray
    ln2: jnp.ndarray
    w1: jnp.ndarray
    w2: jnp.ndarray


def init_gpo_params(cfg: GPOConfig, key) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 4)
    d = cfg.d_model

    def init_layer(k):
        ks = jax.random.split(k, 6)
        return GPOLayer(
            ln1=jnp.zeros((d,), dtype),
            wq=dense_init(ks[0], (d, d), dtype=dtype),
            wk=dense_init(ks[1], (d, d), dtype=dtype),
            wv=dense_init(ks[2], (d, d), dtype=dtype),
            wo=dense_init(ks[3], (d, d), dtype=dtype),
            ln2=jnp.zeros((d,), dtype),
            w1=dense_init(ks[4], (d, cfg.d_ff), dtype=dtype),
            w2=dense_init(ks[5], (cfg.d_ff, d), dtype=dtype),
        )

    layer_keys = jax.random.split(keys[0], cfg.num_layers)
    out_dim = 2 if cfg.learn_sigma else 1
    return {
        # token = [x ; y ; is_context] -> d_model
        "in_proj": dense_init(keys[1], (cfg.d_embed + 2, d), dtype=dtype),
        "layers": jax.vmap(init_layer)(layer_keys),
        "final_norm": jnp.zeros((d,), dtype),
        "head": dense_init(keys[2], (d, out_dim), dtype=dtype),
    }


def _np_mask(num_ctx: int, num_tgt: int) -> jnp.ndarray:
    """Neural-process attention mask (S, S), S = m + t.

    allowed[i, j] = True iff token i may attend token j:
      * j < m (context): always allowed,
      * j >= m: only if i == j (target self-attention).
    """
    s = num_ctx + num_tgt
    is_ctx_col = jnp.arange(s) < num_ctx
    eye = jnp.eye(s, dtype=bool)
    return jnp.broadcast_to(is_ctx_col[None, :], (s, s)) | eye


def gpo_apply(params: dict, cfg: GPOConfig, ctx_x, ctx_y, tgt_x):
    """Predict target preferences.

    ctx_x (m, d_embed), ctx_y (m,), tgt_x (t, d_embed)
    -> (mu (t,), log_sigma (t,) or None)
    Batch with vmap for multiple groups.
    """
    m, t = ctx_x.shape[0], tgt_x.shape[0]
    ctx_tok = jnp.concatenate(
        [ctx_x, ctx_y[:, None], jnp.ones((m, 1), ctx_x.dtype)], axis=-1)
    tgt_tok = jnp.concatenate(
        [tgt_x, jnp.zeros((t, 2), tgt_x.dtype)], axis=-1)
    tokens = jnp.concatenate([ctx_tok, tgt_tok], axis=0)  # (S, d_embed+2)

    x = _mm(tokens, params["in_proj"])  # (S, d)
    h_dim = cfg.head_dim
    nh = cfg.num_heads

    def body(x, layer: GPOLayer):
        layer = GPOLayer(*layer)
        h = rms_norm(x, layer.ln1, cfg.norm_eps)
        s = h.shape[0]
        q = _mm(h, layer.wq).reshape(s, nh, h_dim)
        k = _mm(h, layer.wk).reshape(s, nh, h_dim)
        v = _mm(h, layer.wv).reshape(s, nh, h_dim)
        with jax.named_scope(ATTENTION_SCOPE):
            if cfg.use_pallas_attention:
                # banded flash kernel with a custom VJP (DESIGN.md §4, §8):
                # valid under jax.grad, so training (gpo_loss) and
                # inference share the same tiled path — the dense (heads,
                # S, S) score tensor below is never materialized.
                from repro.kernels import gpo_attention

                att = gpo_attention(q, k, v, num_ctx=m).reshape(s, -1)
            else:
                scores = jnp.einsum("ihd,jhd->hij", q, k) / jnp.sqrt(
                    jnp.asarray(h_dim, jnp.float32))
                scores = jnp.where(_np_mask(m, t)[None], scores, NEG_INF)
                probs = jax.nn.softmax(scores.astype(jnp.float32),
                                       axis=-1).astype(v.dtype)
                att = jnp.einsum("hij,jhd->ihd", probs, v).reshape(s, -1)
        x = x + _mm(att, layer.wo)
        h2 = rms_norm(x, layer.ln2, cfg.norm_eps)
        x = x + _mm(jax.nn.gelu(_mm(h2, layer.w1)), layer.w2)
        return x, None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    out = _mm(x[m:], params["head"])  # (t, 1 or 2)
    mu = out[:, 0]
    log_sigma = out[:, 1] if cfg.learn_sigma else None
    return mu, log_sigma


class GPOPrefix(NamedTuple):
    """Per-layer context K/V from ``gpo_prefill`` — the reusable half of
    a GPO forward pass (DESIGN.md §12).

    The neural-process mask makes the split exact, not approximate:
    context tokens attend ONLY to context tokens, so their hidden states
    — and therefore every layer's context keys/values — are independent
    of whatever targets are later decoded against them. ``k``/``v`` are
    (L, M, nh, hd); rows at positions >= the ``ctx_len`` the prefix was
    built with are padding and must be masked by the consumer."""

    k: jnp.ndarray
    v: jnp.ndarray

    @property
    def num_ctx(self) -> int:
        return self.k.shape[1]


def _key_mask(num_keys: int, ctx_len) -> Optional[jnp.ndarray]:
    """(num_keys,) bool — True for real context positions. ``ctx_len``
    may be a traced scalar (the serving engine batches ragged requests
    padded to a shared bucket); None means every position is real."""
    if ctx_len is None:
        return None
    return jnp.arange(num_keys) < ctx_len


def gpo_prefill(params: dict, cfg: GPOConfig, ctx_x, ctx_y,
                ctx_len=None) -> GPOPrefix:
    """Run the context block alone and cache per-layer K/V.

    ctx_x (M, d_embed), ctx_y (M,) — M may include padding rows, with
    ``ctx_len`` (static or traced scalar) giving the real count; padded
    rows are excluded as attention *keys*, so their (garbage, finite)
    hidden states never influence real rows. Batch with vmap.
    """
    m = ctx_x.shape[0]
    tokens = jnp.concatenate(
        [ctx_x, ctx_y[:, None], jnp.ones((m, 1), ctx_x.dtype)], axis=-1)
    x = _mm(tokens, params["in_proj"])  # (M, d)
    h_dim, nh = cfg.head_dim, cfg.num_heads
    mask = _key_mask(m, ctx_len)

    def body(x, layer: GPOLayer):
        layer = GPOLayer(*layer)
        h = rms_norm(x, layer.ln1, cfg.norm_eps)
        q = _mm(h, layer.wq).reshape(m, nh, h_dim)
        k = _mm(h, layer.wk).reshape(m, nh, h_dim)
        v = _mm(h, layer.wv).reshape(m, nh, h_dim)
        with jax.named_scope(ATTENTION_SCOPE):
            scores = jnp.einsum("ihd,jhd->hij", q, k) / jnp.sqrt(
                jnp.asarray(h_dim, jnp.float32))
            if mask is not None:
                scores = jnp.where(mask[None, None, :], scores, NEG_INF)
            probs = jax.nn.softmax(scores.astype(jnp.float32),
                                   axis=-1).astype(v.dtype)
            att = jnp.einsum("hij,jhd->ihd", probs, v).reshape(m, -1)
        x = x + _mm(att, layer.wo)
        h2 = rms_norm(x, layer.ln2, cfg.norm_eps)
        x = x + _mm(jax.nn.gelu(_mm(h2, layer.w1)), layer.w2)
        return x, (k, v)

    _, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    return GPOPrefix(k=ks, v=vs)


def gpo_decode(params: dict, cfg: GPOConfig, prefix: GPOPrefix, tgt_x,
               ctx_len=None):
    """Decode targets against a cached context prefix.

    tgt_x (T, d_embed) -> (mu (T,), log_sigma (T,) or None). Each target
    token attends to the prefix keys (masked to ``ctx_len``) plus
    itself — an (nh, T, M+1) score tensor instead of the monolithic
    (nh, S, S): prefill work is never repeated, which is the whole
    point of the prefix cache. Padded target rows produce finite
    garbage and must be sliced off by the caller (targets never attend
    to each other, so they cannot perturb real rows). Batch with vmap.
    """
    t = tgt_x.shape[0]
    mctx = prefix.num_ctx
    tokens = jnp.concatenate(
        [tgt_x, jnp.zeros((t, 2), tgt_x.dtype)], axis=-1)
    x = _mm(tokens, params["in_proj"])  # (T, d)
    h_dim, nh = cfg.head_dim, cfg.num_heads
    mask = _key_mask(mctx, ctx_len)

    def body(x, layer_kv):
        layer, kc, vc = layer_kv  # kc/vc (M, nh, hd)
        layer = GPOLayer(*layer)
        h = rms_norm(x, layer.ln1, cfg.norm_eps)
        q = _mm(h, layer.wq).reshape(t, nh, h_dim)
        k_self = _mm(h, layer.wk).reshape(t, nh, h_dim)
        v_self = _mm(h, layer.wv).reshape(t, nh, h_dim)
        with jax.named_scope(ATTENTION_SCOPE):
            inv_sqrt = 1.0 / jnp.sqrt(jnp.asarray(h_dim, jnp.float32))
            sc_ctx = jnp.einsum("ihd,jhd->hij", q, kc) * inv_sqrt
            sc_self = jnp.sum(q * k_self, axis=-1).T[:, :, None] * inv_sqrt
            scores = jnp.concatenate([sc_ctx, sc_self], axis=-1)  # (h,T,M+1)
            if mask is not None:
                full = jnp.concatenate(
                    [mask, jnp.ones((1,), bool)])  # self always attends
                scores = jnp.where(full[None, None, :], scores, NEG_INF)
            probs = jax.nn.softmax(scores.astype(jnp.float32),
                                   axis=-1).astype(v_self.dtype)
            att = (jnp.einsum("hij,jhd->ihd", probs[..., :mctx], vc)
                   + probs[..., mctx:].transpose(1, 0, 2) * v_self)
        x = x + _mm(att.reshape(t, -1), layer.wo)
        h2 = rms_norm(x, layer.ln2, cfg.norm_eps)
        x = x + _mm(jax.nn.gelu(_mm(h2, layer.w1)), layer.w2)
        return x, None

    x, _ = jax.lax.scan(body, x, (params["layers"], prefix.k, prefix.v))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    out = _mm(x, params["head"])  # (T, 1 or 2)
    mu = out[:, 0]
    log_sigma = out[:, 1] if cfg.learn_sigma else None
    return mu, log_sigma


def gpo_loss(params: dict, cfg: GPOConfig, ctx_x, ctx_y, tgt_x, tgt_y):
    """Eq. 1: NLL of target preferences given context (Gaussian p_theta)."""
    mu, log_sigma = gpo_apply(params, cfg, ctx_x, ctx_y, tgt_x)
    if log_sigma is None:
        return jnp.mean(jnp.square(mu - tgt_y))
    inv_var = jnp.exp(-2.0 * log_sigma)
    return jnp.mean(0.5 * inv_var * jnp.square(mu - tgt_y) + log_sigma)


def predict_preferences(params: dict, cfg: GPOConfig, ctx_x, ctx_y, tgt_x,
                        num_options: int) -> jnp.ndarray:
    """Predicted preference distributions per target question.

    tgt_x is (t*A, d_embed) grouped by question (A consecutive options).
    Returns (t, A) rows on the simplex (clip-and-normalize, GPO's eval).
    """
    mu, _ = gpo_apply(params, cfg, ctx_x, ctx_y, tgt_x)
    scores = mu.reshape(-1, num_options)
    scores = jnp.clip(scores, 1e-4, None)
    return scores / scores.sum(axis=-1, keepdims=True)
