"""FedAvg aggregation (paper Eq. 2-3) in three equivalent forms.

1. ``fedavg_stacked`` — single-process simulation: client trees stacked on
   a leading C axis, weighted sum along it (the one-device engine).
2. ``fedavg_allreduce`` — the TPU-native form used inside ``shard_map``:
   each client shard scales its params by p_g and one weighted
   ``lax.psum`` over the client mesh axis *is* the aggregation server
   (DESIGN.md §3). Hierarchical (multi-pod) FedAvg is the same psum over
   ('pod', 'data').
3. ``fedavg_flat`` — flattened-vector form matching the ``fedavg_reduce``
   Pallas kernel contract.

These are the Eq. 2-3 *primitives*; the pluggable server-aggregation
subsystem that generalizes them (delta contract, FedAvgM/FedAdam/
FedYogi, robust trims, adaptive weights) lives in ``core/aggregation.py``
(DESIGN.md §7).
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.utils.pytree import (
    tree_index,
    tree_ravel_clients,
    tree_unflatten_from_vector,
)

PyTree = Any


def normalize_weights(sizes: jnp.ndarray) -> jnp.ndarray:
    """p_g = |D_g| / sum_g' |D_g'|  (Eq. 2).

    The denominator is clamped so an all-zero size vector (the
    empty-survivor round the §11 availability simulator can produce)
    yields all-zero weights instead of NaNs; any real population
    (sum >= 1 sample) is bit-unaffected by the clamp.
    """
    sizes = jnp.asarray(sizes, jnp.float32)
    return sizes / jnp.maximum(jnp.sum(sizes), jnp.float32(1e-12))


def fedavg_stacked(stacked_params: PyTree, weights: jnp.ndarray) -> PyTree:
    """Eq. 3 for client-stacked trees: leaves (C, ...) -> (...)."""
    w = jnp.asarray(weights, jnp.float32)

    def agg(leaf):
        wf = w.reshape((-1,) + (1,) * (leaf.ndim - 1))
        return jnp.sum(leaf.astype(jnp.float32) * wf, axis=0).astype(leaf.dtype)

    return jax.tree.map(agg, stacked_params)


def broadcast_to_clients(params: PyTree, num_clients: int) -> PyTree:
    """Redistribute the global model to every client (server -> clients)."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (num_clients,) + x.shape), params)


def fedavg_allreduce(local_params: PyTree, weight: jnp.ndarray,
                     axis_names: Sequence[str] | str) -> PyTree:
    """Inside shard_map: weighted psum over the client axis/axes.

    ``weight`` is this client's p_g (already normalized across the axis).
    The psum plays the aggregation server; the result is already
    'redistributed' because every shard holds it.
    """
    return jax.tree.map(
        lambda x: jax.lax.psum(x.astype(jnp.float32) * weight, axis_names)
        .astype(x.dtype),
        local_params)


def fedavg_flat(stacked_params: PyTree, weights: jnp.ndarray) -> PyTree:
    """Flattened-vector FedAvg (the Pallas `fedavg_reduce` contract),
    routed through the aggregation registry: the ``fedavg`` strategy's
    ``reduce_flat`` is the single implementation of the weighted flat
    mean (this helper predates the PR 2 registry and used to duplicate
    it). The imports stay lazy to keep the module graph acyclic —
    ``core.aggregation`` imports this module at top level — but the
    aggregator is built PER CALL: a module-level cache here once leaked
    stale strategy state across configs and test runs (built once with
    num_clients=0, never invalidated). The fedavg builder is closure
    assembly only — no tracing — so per-call construction is free."""
    from repro.configs.base import AggConfig
    from repro.core.aggregation import make_aggregator

    like = tree_index(stacked_params, 0)
    vecs = tree_ravel_clients(stacked_params)  # (C, P)
    agg = make_aggregator(AggConfig(), num_clients=int(vecs.shape[0]))
    avg = agg.reduce_flat(vecs, jnp.asarray(weights, jnp.float32))
    return tree_unflatten_from_vector(avg, like)
