"""Model FLOPs of the GPO predictor, counted from its shapes.

A multiply-add is 2 FLOPs. Only the matrix products count: norms,
softmax, GELU, the optimizer and the aggregation are not model FLOPs.
Attention counts what the neural-process mask needs: a context token
attends to the ``m`` context tokens, a target token to those and itself
(the dense path computes all S x S scores; the difference is not model
work). ``m`` and ``t`` are points (tokens), not questions.
"""
from __future__ import annotations


def _layer(model: dict, rows: int, keys: int) -> float:
    """One block over ``rows`` tokens that attend ``keys`` keys in all."""
    d, f = model["d_model"], model["d_ff"]
    proj = 2 * rows * d * d * 4  # q, k, v, o
    attn = 2 * keys * d * 2  # scores and the weighted sum, over all heads
    mlp = 2 * rows * d * f * 2
    return proj + attn + mlp


def _in_proj(model: dict, rows: int) -> float:
    return 2 * rows * (model["d_embed"] + 2) * model["d_model"]


def _head(model: dict, rows: int) -> float:
    return 2 * rows * model["d_model"] * (2 if model["learn_sigma"] else 1)


def forward(model: dict, m: int, t: int) -> float:
    """One request or one training example: ``m`` context and ``t``
    target tokens through the whole predictor."""
    keys = m * m + t * (m + 1)
    return (_in_proj(model, m + t) + model["num_layers"]
            * _layer(model, m + t, keys) + _head(model, t))


def train_step(model: dict, m: int, t: int) -> float:
    """Forward and backward of one example under ``jax.grad``: the
    backward costs twice the forward, except that the input projection
    needs no gradient for its input (the embeddings are data)."""
    return 3 * forward(model, m, t) - _in_proj(model, m + t)


def prefill(model: dict, m: int) -> float:
    """``m`` context tokens through every block (the cached keys and
    values of each layer), with no head."""
    return _in_proj(model, m) + model["num_layers"] * _layer(model, m, m * m)


def decode(model: dict, m: int, t: int) -> float:
    """``t`` target tokens against a cached context of ``m`` tokens."""
    return (_in_proj(model, t) + model["num_layers"]
            * _layer(model, t, t * (m + 1)) + _head(model, t))
