"""The plain reference: the GPO preference predictor (a transformer neural
process, arXiv:2310.11523) and its federated training (arXiv:2503.09925),
written out in ``jax.numpy`` with every matrix product at ``highest``
precision. It imports nothing of the program and takes nothing the program
made: the weights and the survey data come from ``common.py``.

What the configuration states, and the reference follows:

* one token per (question, option) point: ``[x ; y ; is_context]``, with
  ``y = 0`` and ``is_context = 0`` on targets; no positional encoding;
* the neural-process mask: every token attends to the real context
  tokens, and to itself;
* pre-norm blocks with RMS norm scaled by ``1 + s``, tanh-approximated
  GELU, and a linear head whose first output is the predicted share;
* loss: mean squared error of the targets' shares (fixed sigma);
* served rows: each question's option scores clipped at 1e-4 and
  normalised to sum to 1;
* local training: Adam (b1 0.9, b2 0.999, eps 1e-8), each epoch on a fresh
  draw of context and target questions from the client's answered ones;
  aggregation: the server adds the size-weighted mean of client deltas.

Matrix products are at ``highest`` unless a configuration states the
TPU's default precision for its float32 products (``reference_products``
``bf16_operands``): each product then rounds its two operands to
bfloat16 and accumulates in float32, as one MXU pass does, and so does
each product of its gradient, while everything else stays float32.

``dtype`` below float32 and ``levels`` below 127 are the controls: the
same computation in a lower precision.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30
SCALE_FLOOR = 1e-30  # smallest per-channel scale of the int8 weights


BF16_OPERANDS = "bf16_operands"


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _one_pass(spec, a, b):
    """``einsum(spec, a, b)`` as one MXU pass: operands rounded to
    bfloat16, products accumulated in float32. Its gradients are products
    of the same kind, as the TPU computes those of a default-precision
    float32 product."""
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _one_pass_fwd(spec, a, b):
    return _one_pass(spec, a, b), (a, b)


def _one_pass_bwd(spec, res, ct):
    a, b = res
    ins, out = spec.split("->")
    sa, sb = ins.split(",")
    return (_one_pass(f"{out},{sb}->{sa}", ct, b).astype(a.dtype),
            _one_pass(f"{sa},{out}->{sb}", a, ct).astype(b.dtype))


_one_pass.defvjp(_one_pass_fwd, _one_pass_bwd)


def _mm(a, b, precision=HIGHEST):
    """``a`` (S, K) times ``b`` (K, N)."""
    if precision == BF16_OPERANDS:
        return _one_pass("ik,kn->in", a, b)
    return jnp.matmul(a, b, precision=precision)


def _einsum(spec, a, b, precision):
    if precision == BF16_OPERANDS:
        return _one_pass(spec, a, b)
    return jnp.einsum(spec, a, b, precision=precision)


def _rms_norm(x, s, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1 + s)


def _gelu(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1 + jnp.tanh(c * (x + 0.044715 * x * x * x)))


def forward(w: dict, model: dict, ctx_x, ctx_y, ctx_len, tgt_x,
            precision=HIGHEST):
    """Predicted shares ``mu`` (T,) of one request. ``ctx_x`` (M, E) may
    carry padding rows past ``ctx_len``; they are masked as keys.
    ``precision`` is ``highest``, ``bf16_operands`` (see the module
    docstring), or the backend's default for making weights
    (``common.py``)."""
    mm = functools.partial(_mm, precision=precision)
    dt = w["in_proj"].dtype
    m, t = ctx_x.shape[0], tgt_x.shape[0]
    s = m + t
    ctx = jnp.concatenate([ctx_x, ctx_y[:, None], jnp.ones((m, 1), dt)], -1)
    tgt = jnp.concatenate([tgt_x, jnp.zeros((t, 2), dt)], -1)
    x = mm(jnp.concatenate([ctx, tgt], 0).astype(dt), w["in_proj"])
    nh = model["num_heads"]
    hd = model["d_model"] // nh
    eps = model["norm_eps"]
    pos = jnp.arange(s)
    allowed = (pos[None, :] < ctx_len) | (pos[:, None] == pos[None, :])
    for layer in range(model["num_layers"]):
        h = _rms_norm(x, w["ln1"][layer], eps)
        q = mm(h, w["wq"][layer]).reshape(s, nh, hd)
        k = mm(h, w["wk"][layer]).reshape(s, nh, hd)
        v = mm(h, w["wv"][layer]).reshape(s, nh, hd)
        sc = _einsum("ihd,jhd->hij", q, k, precision)
        sc = jnp.where(allowed[None], sc / math.sqrt(hd), NEG_INF)
        p = jax.nn.softmax(sc, axis=-1)
        att = _einsum("hij,jhd->ihd", p, v, precision)
        x = x + mm(att.reshape(s, -1), w["wo"][layer])
        h2 = _rms_norm(x, w["ln2"][layer], eps)
        x = x + mm(_gelu(mm(h2, w["w1"][layer])), w["w2"][layer])
    x = _rms_norm(x, w["final_norm"], eps)
    return mm(x[m:], w["head"])[:, 0]


def rows(mu, num_options: int):
    scores = jnp.clip(mu.reshape(-1, num_options), 1e-4, None)
    return scores / scores.sum(axis=-1, keepdims=True)


# ---------------------------------------------------------------------------
# serving: rows of padded requests, in blocks
# ---------------------------------------------------------------------------
MATRICES = ("in_proj", "wq", "wk", "wv", "wo", "w1", "w2", "head")


def quantized(w: dict, levels: float) -> dict:
    """Weights as served with symmetric per-output-channel integer levels
    (127 for int8, 7 for int4), rounded to nearest, dequantized to f32."""
    out = dict(w)
    for name in MATRICES:
        x = w[name].astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-2) / levels,
                            SCALE_FLOOR)
        q = jnp.clip(jnp.round(x / scale[..., None, :]), -levels, levels)
        out[name] = q * scale[..., None, :]
    return out


@functools.partial(jax.jit, static_argnames=("model_items", "num_options",
                                             "dtype", "precision"))
def _rows_block(w, model_items, num_options, dtype, precision, ctx_x, ctx_y,
                ctx_len, tgt_x):
    model = dict(model_items)
    w = jax.tree.map(lambda a: a.astype(dtype), w)

    def one(cx, cy, cl, tx):
        mu = forward(w, model, cx.astype(dtype), cy.astype(dtype), cl,
                     tx.astype(dtype), precision=precision)
        return rows(mu.astype(jnp.float32), num_options)

    return jax.vmap(one)(ctx_x, ctx_y, ctx_len, tgt_x)


def serve_rows(w: dict, model: dict, num_options: int, requests, *,
               m_pad: int, t_pad: int, dtype=jnp.float32,
               levels: float | None = None, precision=HIGHEST,
               block: int = 16):
    """Reference rows for ``requests``: a list of (ctx_x, ctx_y, tgt_x)
    numpy arrays, padded to (``m_pad``, ``t_pad``) points and computed
    ``block`` requests at a time."""
    import numpy as np

    if levels is not None:
        w = quantized(w, levels)
    m_max, t_max = m_pad, t_pad
    e = requests[0][0].shape[1]
    items = tuple(sorted(model.items()))
    out = []
    for i in range(0, len(requests), block):
        chunk = requests[i:i + block]
        n = len(chunk)
        cx = np.zeros((block, m_max, e), np.float32)
        cy = np.zeros((block, m_max), np.float32)
        cl = np.zeros((block,), np.int32)
        tx = np.zeros((block, t_max, e), np.float32)
        for j, (a, b, c) in enumerate(chunk):
            cx[j, :a.shape[0]], cy[j, :a.shape[0]] = a, b
            cl[j] = a.shape[0]
            tx[j, :c.shape[0]] = c
        got = np.asarray(_rows_block(w, items, num_options, dtype,
                                     precision, cx, cy, cl, tx))
        out.extend(got[j, :chunk[j][2].shape[0] // num_options]
                   for j in range(n))
    return out


# ---------------------------------------------------------------------------
# federated training
# ---------------------------------------------------------------------------
def _adam(w, g, m, v, step, lr):
    """One Adam step on every leaf; results stay in the leaves' dtype."""
    b1, b2, eps = 0.9, 0.999, 1e-8
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)
    m = jax.tree.map(lambda a, b: (b1 * a + (1 - b1) * b).astype(a.dtype),
                     m, g)
    v = jax.tree.map(lambda a, b: (b2 * a + (1 - b2) * b * b).astype(a.dtype),
                     v, g)
    w = jax.tree.map(
        lambda p, a, b: (p - lr * (a / bc1) / (jnp.sqrt(b / bc2) + eps)
                         ).astype(p.dtype), w, m, v)
    return w, m, v


@functools.partial(jax.jit, static_argnames=("model_items", "fed_items",
                                             "rounds", "dtype", "precision"))
def _fed(w0, survey, train_groups, key, model_items, fed_items, rounds,
         dtype, precision):
    model, fed = dict(model_items), dict(fed_items)
    n_ctx, n_tgt = fed["num_context"], fed["num_target"]
    epochs, lr = fed["local_epochs"], fed["lr"]
    phi = survey["phi"].astype(dtype)
    prefs = survey["prefs"].astype(dtype)
    mask, sizes = survey["mask"], survey["sizes"]
    n_q, e = phi.shape[0], phi.shape[-1]
    c = train_groups.shape[0]
    w_c = sizes[train_groups].astype(jnp.float32)
    w_c = (w_c / jnp.sum(w_c)).astype(dtype)

    def loss_fn(w, ctx_x, ctx_y, tgt_x, tgt_y):
        mu = forward(w, model, ctx_x, ctx_y, ctx_x.shape[0], tgt_x,
                     precision=precision)
        return jnp.mean(jnp.square(mu - tgt_y))

    def epoch(carry, k, group):
        w, m, v, step = carry
        p = jax.nn.softmax(jnp.where(mask[group], 0.0, -1e9))
        qs = jax.random.choice(k, n_q, shape=(n_ctx + n_tgt,), replace=False,
                               p=p)
        cq, tq = qs[:n_ctx], qs[n_ctx:]
        loss, g = jax.value_and_grad(loss_fn)(
            w, phi[cq].reshape(-1, e), prefs[group, cq].reshape(-1),
            phi[tq].reshape(-1, e), prefs[group, tq].reshape(-1))
        step = step + 1
        w, m, v = _adam(w, g, m, v, step, lr)
        return (w, m, v, step), loss

    def client(w, m, v, step, k, group):
        ks = jax.random.split(k, epochs)
        (w, m, v, step), losses = jax.lax.scan(
            lambda cr, kk: epoch(cr, kk, group), (w, m, v, step), ks)
        return w, m, v, step, jnp.mean(losses.astype(jnp.float32))

    def round_(carry, _):
        g, m, v, step, key = carry
        key, k_round, _ = jax.random.split(key, 3)
        _, k_train = jax.random.split(k_round)
        keys = jax.random.split(k_train, c)
        bcast = jax.tree.map(lambda a: jnp.broadcast_to(a, (c,) + a.shape), g)
        w, m, v, step, losses = jax.vmap(client)(bcast, m, v, step, keys,
                                                 train_groups)
        g = jax.tree.map(
            lambda gl, wl: (gl + jnp.tensordot(w_c, wl - gl[None], axes=1,
                                               precision=HIGHEST)
                            ).astype(dtype), g, w)
        return (g, m, v, step, key), jnp.mean(losses)

    g0 = jax.tree.map(lambda a: a.astype(dtype), w0)
    zeros = jax.tree.map(lambda a: jnp.zeros((c,) + a.shape, dtype), g0)
    step0 = jnp.zeros((c,), jnp.int32)
    (g, m, _, _, _), losses = jax.lax.scan(
        round_, (g0, zeros, zeros, step0, key), None, length=rounds)
    return losses, g, m


def fed_train(w0: dict, survey: dict, train_groups, model: dict, fed: dict,
              seed: int, rounds: int, dtype=jnp.float32,
              precision=HIGHEST):
    """``rounds`` rounds of the paper's federation from ``w0``, with the
    round keys of a run seeded with ``seed``. Returns the mean client loss
    of each round (R,), the final global weights, and each client's Adam
    first moment (C, ...)."""
    fed_keys = ("num_context", "num_target", "local_epochs", "lr")
    return _fed(w0, {k: survey[k] for k in ("phi", "prefs", "mask", "sizes")},
                jnp.asarray(train_groups, jnp.int32),
                jax.random.PRNGKey(seed + 1),
                tuple(sorted(model.items())),
                tuple((k, fed[k]) for k in fed_keys), rounds, dtype,
                precision)
