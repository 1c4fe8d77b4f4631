"""Frozen-LLM embedding frontends for the preference pipeline.

The paper embeds each concatenated (question, answer) text with Alpaca-7B
once per group before training (§4.3). Offline we provide:

* ``StubEmbedder`` — deterministic pseudo-embeddings (hash -> PRNG -> unit
  normal). This is the declared frontend stub: weak-type-correct, the right
  shape, zero model weights.
* ``BackboneEmbedder`` — runs any model-zoo backbone (mean-pooled final
  hidden state) so the full pipeline (backbone -> GPO -> FedAvg) is
  exercised end-to-end with real compute in examples/tests on reduced
  configs, and on TPU with the full assigned architectures.
* ``masked_mean`` and ``unit_norm`` — the pool and the normalisation
  both that embedder and the serving engine's text path
  (``core/serving.py``) apply to a backbone's final hidden states.
"""
from __future__ import annotations

import hashlib
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


class StubEmbedder:
    """Deterministic stand-in for the frozen Alpaca-7B embedding function."""

    def __init__(self, d_embed: int, seed: int = 0):
        self.d_embed = d_embed
        self.seed = seed

    def _key_for(self, text: str) -> jax.Array:
        h = int.from_bytes(
            hashlib.sha256(text.encode()).digest()[:4], "little")
        return jax.random.fold_in(jax.random.PRNGKey(self.seed), h)

    def embed_text(self, text: str) -> jnp.ndarray:
        v = jax.random.normal(self._key_for(text), (self.d_embed,))
        return v / jnp.linalg.norm(v)

    def embed_qa(self, question: str, answer: str) -> jnp.ndarray:
        return self.embed_text(question + " [SEP] " + answer)

    def embed_batch(self, texts: list[str]) -> jnp.ndarray:
        return jnp.stack([self.embed_text(t) for t in texts])


def masked_mean(hidden: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """(B, S, d) hidden states, (B, S) 0/1 mask -> (B, d) mean over the
    masked positions (0 for a row with none)."""
    denom = jnp.maximum(mask.sum(axis=1, keepdims=True), 1)
    return (hidden * mask[..., None]).sum(axis=1) / denom


def unit_norm(x: jnp.ndarray) -> jnp.ndarray:
    return x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-6)


class BackboneEmbedder:
    """Embed token sequences with a frozen model-zoo backbone.

    ``apply_fn(params, tokens) -> (B, S, d_model)`` is the backbone's hidden
    state function; embeddings are masked mean-pools projected to d_embed.
    """

    def __init__(self, apply_fn: Callable, params, d_model: int, d_embed: int,
                 seed: int = 0):
        self.apply_fn = apply_fn
        self.params = params
        proj_key = jax.random.PRNGKey(seed)
        self.proj = (jax.random.normal(proj_key, (d_model, d_embed))
                     / np.sqrt(d_model)) if d_model != d_embed else None
        self._jit_embed = jax.jit(self._embed)

    def _embed(self, tokens: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
        hidden = self.apply_fn(self.params, tokens)  # (B, S, d_model)
        pooled = masked_mean(hidden, mask)
        if self.proj is not None:
            pooled = pooled @ self.proj
        return unit_norm(pooled)

    def embed_tokens(self, tokens: jnp.ndarray,
                     mask: jnp.ndarray | None = None) -> jnp.ndarray:
        if mask is None:
            mask = jnp.ones(tokens.shape[:2], jnp.float32)
        return self._jit_embed(tokens, mask)
