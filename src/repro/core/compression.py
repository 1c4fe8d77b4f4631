"""Communication-efficient client-delta transport (DESIGN.md §10).

The compression stage sits on the client→server path BETWEEN the privacy
pipeline and the ``ServerAggregator``: each client's flat delta d_c is
released by the DP pipeline (clip + noise, ``core/privacy.py``), the
EF residual is folded in, the result is compressed and immediately
decompressed (the server consumes the "transmitted" values t_c), and the
aggregator reduces the t_c:

    d̃_c = privacy_release(d_c)          (unchanged — ε is unaffected,
                                          compression is post-processing)
    u_c  = d̃_c + e_c                     (EF21-style residual carry-in)
    t_c  = D(Q(u_c))                     (codec round trip)
    e'_c = u_c − t_c                     (residual carry-out)
    Δ    = aggregate_c(w_c, t_c)

Codecs (``CompressionConfig.kind``):

* ``int8`` — per-client symmetric quantization to 127 levels, scale
  s_c = max|u_c| / 127. Stochastic rounding q = ⌊u/s + υ⌋ with
  υ ~ U[0,1) is unbiased (E[t] = u); υ is PRESAMPLED outside any kernel
  from keys folded out of the per-client TRAINING keys (tag
  ``_QUANT_TAG``), exactly the noise-key scheme of §9 — so both
  ``FederatedGPO`` drivers and ``make_sharded_round`` draw bit-identical
  rounding randomness from the same round keys, and the fused Pallas
  kernel reproduces the jnp path / ``ref.py`` oracle exactly.
* ``topk`` — magnitude sparsification: entries below the per-client
  ⌈topk_frac·P⌉-th largest |u_c| are zeroed (threshold ties kept). The
  threshold is a global selection (``lax.top_k``) and cannot stream; the
  Pallas ``topk_reduce`` kernel fuses the mask/scatter + weighted reduce
  (+ residual write) that follows it.

On the wire: the sharded engine's robust-aggregator family all-gathers
the int8 payload + f32 per-client scales instead of f32 vectors — P + 4
bytes per client instead of 4P, ~4× fewer bytes on the round's dominant
collective. The linear family dequantizes shard-locally and keeps its
single f32 psum (the psum models the server's reduction, not the
client upload; the byte accounting lives in DESIGN.md §10).
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import CompressionConfig, PrivacyConfig
from repro.core import privacy as dp
from repro.kernels import agg_quant_clip_reduce, agg_topk_reduce
# shared contract constants (see the _NORM_FLOOR note in core/privacy.py:
# imported so the jnp path and the kernels cannot drift; the ref.py
# oracles restate the literals by design)
from repro.kernels.agg_reduce import INT8_LEVELS, _SCALE_FLOOR

PyTree = Any

# fold_in tag deriving a client's stochastic-rounding key from its local
# training key; distinct from privacy's _NOISE_TAG so the rounding
# uniforms are independent of the DP noise.
_QUANT_TAG = 0x0C0DEC


def client_quant_keys(keys: jnp.ndarray) -> jnp.ndarray:
    """Per-client rounding keys derived from the per-client training
    keys (the §9 noise-key scheme with a different tag)."""
    return jax.vmap(lambda k: jax.random.fold_in(k, _QUANT_TAG))(keys)


def client_uniform(keys: jnp.ndarray, shape: tuple) -> jnp.ndarray:
    """Presampled U[0,1) stochastic-rounding tile (C, P); ``keys`` are
    the per-client TRAINING keys (rounding keys are folded from them)."""
    qkeys = client_quant_keys(keys)
    return jax.vmap(
        lambda k: jax.random.uniform(k, shape[1:], jnp.float32))(qkeys)


# ---------------------------------------------------------------------------
# codec primitives on the flat (C, P) matrix
# ---------------------------------------------------------------------------
def quantize_int8(vecs: jnp.ndarray, *,
                  uniform: Optional[jnp.ndarray] = None
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(C, P) f32 -> (q int8 (C, P), scales f32 (C,)). Symmetric
    127-level grid; stochastic rounding when a presampled ``uniform``
    tile is given, round-to-nearest otherwise. The scale floor keeps
    all-zero clients at exact zeros."""
    x = vecs.astype(jnp.float32)
    scales = jnp.maximum(jnp.max(jnp.abs(x), axis=1) / INT8_LEVELS,
                         _SCALE_FLOOR)
    z = x / scales[:, None]
    q = (jnp.floor(z + uniform.astype(jnp.float32)) if uniform is not None
         else jnp.round(z))
    q = jnp.clip(q, -INT8_LEVELS, INT8_LEVELS)
    return q.astype(jnp.int8), scales


def dequantize_int8(q: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    """(C, P) int8 + (C,) scales -> (C, P) f32 transmitted values."""
    return q.astype(jnp.float32) * scales[:, None]


def topk_count(p: int, frac: float) -> int:
    """Entries kept per client: ⌈frac·P⌉, at least 1."""
    return max(1, int(math.ceil(frac * p)))


def topk_thresholds(vecs: jnp.ndarray, frac: float) -> jnp.ndarray:
    """(C,) per-client magnitude threshold: the k-th largest |value|."""
    k = topk_count(vecs.shape[1], frac)
    mags = jnp.abs(vecs.astype(jnp.float32))
    return jax.lax.top_k(mags, k)[0][:, -1]


def sparsify_topk(vecs: jnp.ndarray, frac: float
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(C, P) -> (sparsified (C, P) f32, thresholds (C,)): zero every
    entry whose magnitude sits below the top-k threshold (ties kept)."""
    x = vecs.astype(jnp.float32)
    tau = topk_thresholds(x, frac)
    return jnp.where(jnp.abs(x) >= tau[:, None], x, 0.0), tau


def compress_flat(vecs: jnp.ndarray, keys: Optional[jnp.ndarray],
                  comp: CompressionConfig) -> jnp.ndarray:
    """Codec round trip D(Q(·)) on the (C, P) matrix — the transmitted
    values the server consumes (jnp reference path; oracles in
    kernels/ref.py restate the same math)."""
    if comp.kind == "int8":
        uniform = (client_uniform(keys, vecs.shape) if comp.stochastic
                   else None)
        return dequantize_int8(*quantize_int8(vecs, uniform=uniform))
    if comp.kind == "topk":
        return sparsify_topk(vecs, comp.topk_frac)[0]
    return vecs.astype(jnp.float32)


def ef_compress_flat(vecs: jnp.ndarray, keys: Optional[jnp.ndarray],
                     comp: CompressionConfig,
                     resid: Optional[jnp.ndarray]
                     ) -> tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """EF21-style wrapper: compress(d̃ + e), e' = (d̃ + e) − t.
    ``resid=None`` (error feedback off) is a plain codec round trip."""
    u = vecs.astype(jnp.float32)
    if resid is not None:
        u = u + resid
    t = compress_flat(u, keys, comp)
    return t, (u - t if resid is not None else None)


def release_flat(vecs: jnp.ndarray, keys: Optional[jnp.ndarray],
                 privacy, comp: CompressionConfig,
                 resid: Optional[jnp.ndarray]
                 ) -> tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Per-client released values WITHOUT the client-axis reduction:
    DP release (if enabled) then the EF/codec round trip, returning the
    (C, P) transmitted matrix and the carry-out residual. The fault-
    aware round (DESIGN.md §11) needs each client's wire value
    individually — straggler payloads are buffered whole and lost
    clients are masked after the fact — so the fused reduce-style
    kernels don't apply here; the rows are bit-identical to the jnp
    path of ``transport_delta_flat``."""
    x = vecs.astype(jnp.float32)
    if privacy.enabled:
        x = dp.privatize_flat(x, keys, privacy)
    if not comp.enabled:
        return x, resid
    return ef_compress_flat(x, keys, comp, resid)


# ---------------------------------------------------------------------------
# the full transport for client-stacked engines
# ---------------------------------------------------------------------------
def transport_delta_flat(vecs: jnp.ndarray, weights: jnp.ndarray,
                         keys: Optional[jnp.ndarray],
                         privacy: PrivacyConfig, comp: CompressionConfig,
                         agg, resid: Optional[jnp.ndarray], *,
                         use_pallas: bool = False
                         ) -> tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """DP release → EF/compress → client-axis reduction on the raw flat
    (C, P) delta matrix. Returns (delta_vec (P,), new residual | None).

    Engines that hold every client locally (the stacked GPO drivers and
    the backbone/LoRA trainers) call this whole chain; the sharded
    engine calls it per shard for the linear family (its psum rides
    after) and inlines the codec around its all-gather for the robust
    family (the int8 payload is what crosses the wire there).

    ``use_pallas`` routes the linear family through ONE fused kernel:
    ``agg_quant_clip_reduce`` for int8 (clip/noise/EF/quantize/reduce in
    a single launch, no (C, P) intermediate in HBM) or the top-k
    threshold/scatter kernel after the jnp threshold selection. The
    robust family privatizes + compresses in jnp and reduces through
    ``agg.reduce_flat`` (which is the rank-trim kernel under the same
    flag).
    """
    x = vecs.astype(jnp.float32)
    w = weights.astype(jnp.float32)
    if comp.kind == "int8":
        uniform = (client_uniform(keys, x.shape) if comp.stochastic
                   else None)
        if use_pallas and agg.linear:
            noise = (dp.client_noise(keys, x.shape, privacy.sigma)
                     if privacy.enabled and privacy.noise_multiplier > 0.0
                     else None)
            clip = privacy.clip_norm if privacy.enabled else 0.0
            return agg_quant_clip_reduce(x, w, clip=clip, noise=noise,
                                         uniform=uniform, resid=resid)
        if privacy.enabled:
            x = dp.privatize_flat(x, keys, privacy)
        u = x + resid if resid is not None else x
        t = dequantize_int8(*quantize_int8(u, uniform=uniform))
    elif comp.kind == "topk":
        if privacy.enabled:
            x = dp.privatize_flat(x, keys, privacy)
        u = x + resid if resid is not None else x
        if use_pallas and agg.linear:
            tau = topk_thresholds(u, comp.topk_frac)
            return agg_topk_reduce(u, w, tau,
                                   with_residual=resid is not None)
        t = jnp.where(
            jnp.abs(u) >= topk_thresholds(u, comp.topk_frac)[:, None],
            u, 0.0)
    else:
        raise ValueError(f"transport called with kind={comp.kind!r} "
                         "(callers must gate on CompressionConfig.enabled)")
    new_resid = u - t if resid is not None else None
    # registry reduce: the linear family's weighted flat mean or the
    # robust family's rank trim (kernel-backed under use_pallas — the
    # fused-transport kernels intercepted the linear+pallas paths above)
    return agg.reduce_flat(t, w), new_resid
