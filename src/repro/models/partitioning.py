"""Logical activation-sharding rules (MaxText-style).

Model code annotates activations with *logical* axis names
(``shard_act(x, ("batch", "seq", "heads", "hd"))``); a context installed by
the launcher maps logical names to mesh axes with divisibility guards.
Without a context (unit tests, CPU experiments) the calls are identity.

Why this exists: without explicit constraints GSPMD is free to shard an
attention contraction dimension, which materializes *partial* full-size
score tensors and all-reduces them. Pinning activations to
batch->data, heads/ff/vocab->model (only when divisible) makes XLA move
weights (small) instead of activations (huge) — the standard production
layout.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Optional, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_STATE = threading.local()


def default_rules(mesh) -> dict:
    batch = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    return {
        "batch": batch if len(batch) > 1 else (batch[0] if batch else None),
        "heads": "model",
        "kv_heads": "model",
        "ff": "model",
        "vocab": "model",
        "experts": "model",
        "capacity": batch if len(batch) > 1 else (batch[0] if batch else None),
        "ssm_heads": "model",
        # query-sequence parallelism: used when NO head dim divides the
        # model axis (llava 56H/8KV, qwen2 14H/2KV) — scores shard on the
        # query dim instead of being replicated
        "seq_q": "model",
        # replicated logical axes
        "seq": None,
        "embed": None,
        "hd": None,
        "state": None,
    }


@contextmanager
def activation_sharding(mesh, rules: Optional[dict] = None):
    prev = getattr(_STATE, "ctx", None)
    _STATE.ctx = (mesh, rules or default_rules(mesh))
    try:
        yield
    finally:
        _STATE.ctx = prev


def _axis_size(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def logical_axis_size(name: str) -> int:
    """Mesh size the given logical axis maps to (1 without a context)."""
    ctx = getattr(_STATE, "ctx", None)
    if ctx is None:
        return 1
    mesh, rules = ctx
    return _axis_size(mesh, rules.get(name))


def prefers_repeat_kv(num_heads: int, num_kv_heads: int) -> bool:
    """GQA layout choice under the installed sharding context.

    When Q-heads divide the model axis but KV-heads do not (qwen3: 64/8 on
    a 16-way axis), the grouped (b,s,kv,g,hd) form splits the shardable
    head dim into two unshardable factors and GSPMD must replicate the
    O(S^2) score tensor (measured: 35 TB of all-gather per qwen3-32b 32k
    prefill). Repeating KV to the full head count keeps one clean
    'heads' dim instead — tiny KV duplication, zero score gathers.
    """
    size = logical_axis_size("heads")
    if size <= 1:
        return False
    return num_heads % size == 0 and num_kv_heads % size != 0


def prefers_q_sharding(num_heads: int, num_kv_heads: int) -> bool:
    """Neither head dim divides the model axis: shard attention on the
    query-sequence dim instead (valid for any head count; the per-dim
    divisibility guard in shard_act skips decode's q-length of 1)."""
    size = logical_axis_size("heads")
    if size <= 1:
        return False
    return num_heads % size != 0 and num_kv_heads % size != 0


def shard_act(x, logical: Sequence[Optional[str]]):
    """Constrain ``x`` to the logical spec under the installed context.

    Identity when no context is installed or x is not a jax array-like.
    Dims whose size the mapped mesh axes do not divide are left unsharded.
    """
    ctx = getattr(_STATE, "ctx", None)
    if ctx is None:
        return x
    mesh, rules = ctx
    if len(logical) != x.ndim:
        return x
    spec = []
    for dim, name in zip(x.shape, logical):
        axis = rules.get(name) if name else None
        if axis is not None and dim % _axis_size(mesh, axis) == 0:
            spec.append(axis)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))
