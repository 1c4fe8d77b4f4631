"""Sharding rules: parameter / optimizer-state / activation / cache
PartitionSpecs for every architecture on the production meshes.

Conventions (DESIGN.md §6):

* `model` axis: tensor parallelism — attention heads (via the fused q/kv
  projection columns), d_ff, experts (when E divides the axis), vocab.
* `data` axis (+ `pod` on multi-pod): batch / FedAvg clients; with
  ``fsdp=True`` the *frozen or adafactor-trained* parameter matrices also
  shard their second dimension over it (ZeRO-3 style) — required for the
  >=27B archs to fit 16 GB/chip.
* every rule is divisibility-guarded: a dim is sharded only if the axis
  size divides it, otherwise the next candidate (or replication) is used —
  e.g. mamba2's vocab 50280 is not 16-divisible, so its embedding shards
  d_model instead.
"""
from __future__ import annotations

import re
from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

PyTree = Any


def _axis_size(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def _guarded(mesh, shape, assignment: dict[int, Any]) -> P:
    """Build a PartitionSpec keeping only divisible assignments."""
    spec = [None] * len(shape)
    for dim, axis in assignment.items():
        if axis is None:
            continue
        if shape[dim] % _axis_size(mesh, axis) == 0:
            spec[dim] = axis
    return P(*spec)


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------
def param_spec(path: str, shape: tuple, cfg: ModelConfig, mesh, *,
               fsdp: bool) -> P:
    """PartitionSpec for one parameter leaf. ``path`` is the jax keystr.

    Stacked per-layer leaves carry a leading L (or super-block) batch of
    dims which are never sharded; rules address the trailing dims.
    """
    d_axis = "data" if (fsdp and "data" in mesh.axis_names) else None
    nd = len(shape)
    last, sec = nd - 1, nd - 2

    def tail_matmul(in_axis, out_axis):
        return _guarded(mesh, shape, {sec: in_axis, last: out_axis})

    if re.search(r"\bembed\b|'embed'", path) or path.endswith("['embed']"):
        # (V, d): vocab over model if divisible; otherwise REPLICATE —
        # sharding the gathered (trailing) dim trips an XLA SPMD
        # dynamic-slice verifier bug inside scanned train steps (observed
        # on granite/whisper, vocab % 16 != 0), and the non-divisible
        # vocabs all belong to <1B archs where a replicated embed is cheap.
        if shape[0] % _axis_size(mesh, "model") == 0:
            return _guarded(mesh, shape, {0: "model", 1: d_axis})
        return P(*([None] * len(shape)))
    if "lm_head" in path:
        if shape[last] % _axis_size(mesh, "model") == 0:
            return tail_matmul(d_axis, "model")
        return tail_matmul("model", None)
    if "router" in path:
        return tail_matmul(d_axis, None)
    routed = "['moe']" in path  # not a dense MLP or the shared expert
    if re.search(r"w_gate|w_up", path):
        if routed and nd >= 3:
            # (L, E, d, ff): expert-parallel when E divides model axis
            e_dim = nd - 3
            if shape[e_dim] % _axis_size(mesh, "model") == 0:
                return _guarded(mesh, shape, {e_dim: "model", sec: d_axis})
            return tail_matmul(d_axis, "model")
        return tail_matmul(d_axis, "model")
    if "w_down" in path:
        if routed and nd >= 3:
            e_dim = nd - 3
            if shape[e_dim] % _axis_size(mesh, "model") == 0:
                return _guarded(mesh, shape, {e_dim: "model", last: d_axis})
            return tail_matmul("model", d_axis)
        return tail_matmul("model", d_axis)
    if re.search(r"\bwq\b|'wq'|\bwk\b|'wk'|\bwv\b|'wv'|in_proj", path):
        return tail_matmul(d_axis, "model")
    if re.search(r"\bwo\b|'wo'|out_proj", path):
        return tail_matmul("model", d_axis)
    if re.search(r"'b[qkv]'", path):
        return _guarded(mesh, shape, {last: "model"})
    # norms, conv, dt_bias, A_log, D, small vectors: replicated
    return P(*([None] * nd))


def params_shardings(params_shapes: PyTree, cfg: ModelConfig, mesh, *,
                     fsdp: bool) -> PyTree:
    def assign(path, leaf):
        return NamedSharding(
            mesh, param_spec(path, tuple(leaf.shape), cfg, mesh, fsdp=fsdp))

    return jax.tree_util.tree_map_with_path(
        lambda p, l: assign(jax.tree_util.keystr(p), l), params_shapes)


# ---------------------------------------------------------------------------
# Optimizer-state rules (state trees mirror params leaf-for-leaf)
# ---------------------------------------------------------------------------
def adam_state_shardings(p_shard: PyTree, mesh):
    """AdamState(step, mu, nu): mu/nu mirror the param shardings."""
    from repro.optim.optimizers import AdamState

    return AdamState(step=NamedSharding(mesh, P()), mu=p_shard, nu=p_shard)


def server_state_shardings(state: PyTree, mesh) -> PyTree:
    """Server-aggregator ``AggState`` is replicated on every shard
    (DESIGN.md §7): the post-psum server update is deterministic, so each
    client shard carries the momentum/moment trees and adaptive scores
    whole rather than paying a gather before every round. This covers
    the DP pipeline (DESIGN.md §9) too: clip/noise state is either
    shard-local (per-client noise keys fold out of the sharded ``keys``
    argument) or host-side (the Rényi accountant), so a private round
    adds NO device-resident server state — any pytree handed here (e.g.
    a future accountant-on-device extension) replicates the same way."""
    repl = NamedSharding(mesh, P())
    return jax.tree.map(lambda _: repl, state)


def client_delta_sharding(mesh, client_axes=None) -> NamedSharding:
    """Sharding for client-stacked round arguments — (C, ...) leaves
    whose leading axis is the global client axis — on a federation mesh:
    the leading dim shards over the client axes, ('edge', 'data') on the
    §14 two-level edge mesh, ('pod', 'data') multi-pod, ('data',)
    otherwise (``client_axes=None`` derives them from the mesh via
    ``launch.mesh.client_axes``)."""
    from repro.launch.mesh import client_axes as _client_axes

    ax = tuple(client_axes) if client_axes else _client_axes(mesh)
    return NamedSharding(mesh, P(ax if len(ax) > 1 else ax[0]))


def fault_state_shardings(mesh, client_axes=("data",)) -> PyTree:
    """Shardings for ``core.availability.FaultState`` on the production
    mesh (DESIGN.md §11). The schedule metadata — round counter, crash-
    rejoin gates, pending due/weight/birth vectors — is replicated: every
    shard recomputes the full-population failure schedule from the
    replicated fault key, so no collective is spent agreeing on who
    failed. Only ``pending`` (the in-flight straggler payloads, the one
    parameter-sized leaf, (C, P)) shards over the client axes with its
    owners — multi-axis layouts (('pod', 'data'), or the §14
    ('edge', 'data') edge mesh) pass straight through."""
    from repro.core.availability import FaultState

    ax = tuple(client_axes)
    repl = NamedSharding(mesh, P())
    return FaultState(
        round=repl,
        offline_until=repl,
        pending=NamedSharding(mesh, P(ax if len(ax) > 1 else ax[0])),
        pending_due=repl,
        pending_weight=repl,
        pending_birth=repl)


def byz_key_sharding(mesh) -> NamedSharding:
    """Sharding for the round's Byzantine key (DESIGN.md §13): REPLICATED,
    like the §11 fault key — every shard derives the full-population
    attacker mask from the one (2,) uint32 key (``adversary.
    fold_byz_key`` of the round key), spending no collective on agreeing
    who is corrupt. It is the round's LAST trailing argument (after the
    EF residual, when present)."""
    return NamedSharding(mesh, P())


def adafactor_state_shardings(p_shard: PyTree, params_shapes: PyTree, mesh):
    """AdafactorState: v_row drops the param's last dim, v_col its
    second-to-last; v_full only exists for <2-D leaves (replicated)."""
    from repro.optim.optimizers import AdafactorState

    scalar = NamedSharding(mesh, P())

    def row_one(sh: NamedSharding, shape):
        if len(shape.shape) >= 2:
            spec = tuple(sh.spec)
            return NamedSharding(mesh, P(*spec[:-1]))
        return scalar

    def col_one(sh: NamedSharding, shape):
        if len(shape.shape) >= 2:
            spec = tuple(sh.spec)
            return NamedSharding(mesh, P(*(spec[:-2] + spec[-1:])))
        return scalar

    def full_one(sh: NamedSharding, shape):
        return scalar if len(shape.shape) >= 2 else sh

    v_row = jax.tree.map(row_one, p_shard, params_shapes)
    v_col = jax.tree.map(col_one, p_shard, params_shapes)
    v_full = jax.tree.map(full_one, p_shard, params_shapes)
    return AdafactorState(step=scalar, v_row=v_row, v_col=v_col,
                          v_full=v_full)


# ---------------------------------------------------------------------------
# Activation / batch / cache rules
# ---------------------------------------------------------------------------
def batch_shardings(batch_shapes: dict, mesh, batch_axes) -> dict:
    """tokens/labels (B, S[, d]): B over the data axes (replicate if B==1)."""
    out = {}
    for k, v in batch_shapes.items():
        b = v.shape[0]
        ax = batch_axes if b % _axis_size(mesh, tuple(batch_axes)) == 0 \
            else None
        spec = [None] * len(v.shape)
        if ax:
            spec[0] = tuple(ax) if len(ax) > 1 else ax[0]
        out[k] = NamedSharding(mesh, P(*spec))
    return out


def cache_shardings(cache_shapes: dict, cfg: ModelConfig, mesh,
                    batch_axes) -> dict:
    """Decode caches.

    * batch > 1: batch over data axes; KV heads over model when divisible,
      else the sequence axis over model (flash-decode style partial
      attention, GSPMD inserts the combine).
    * batch == 1 (long_500k): the cache SEQUENCE axis carries the
      parallelism — over (data x model) when KV heads don't divide model,
      else seq over data + KV over model.
    """
    m = _axis_size(mesh, "model")
    d_ax = tuple(batch_axes)
    out = {}
    for k, v in cache_shapes.items():
        shape = v.shape
        spec = [None] * len(shape)
        if k in ("ring_k", "ring_v", "glob_k", "glob_v"):
            # (n_super, n_per, B, S|W, KV, hd): batch over data, KV over
            # model when divisible, else the length axis over model
            _, _, B, S, KV, _ = shape
            if B % _axis_size(mesh, d_ax) == 0 and B > 1:
                spec[2] = d_ax if len(d_ax) > 1 else d_ax[0]
            if KV % m == 0:
                spec[4] = "model"
            elif S % m == 0:
                spec[3] = "model"
            out[k] = NamedSharding(mesh, P(*spec))
            continue
        if k in ("tail_k", "tail_v"):
            _, B, S, KV, _ = shape
            if B % _axis_size(mesh, d_ax) == 0 and B > 1:
                spec[1] = d_ax if len(d_ax) > 1 else d_ax[0]
            if KV % m == 0:
                spec[3] = "model"
            elif S % m == 0:
                spec[2] = "model"
            out[k] = NamedSharding(mesh, P(*spec))
            continue
        if k in ("k", "v", "cross_k", "cross_v", "shared_k", "shared_v"):
            L, B, S, KV, hd = shape
            big_batch = B % _axis_size(mesh, d_ax) == 0 and B > 1
            if big_batch:
                spec[1] = d_ax if len(d_ax) > 1 else d_ax[0]
                if KV % m == 0:
                    spec[3] = "model"
                elif S % m == 0:
                    spec[2] = "model"
            else:
                if KV % m == 0:
                    spec[3] = "model"
                    if S % _axis_size(mesh, d_ax) == 0:
                        spec[2] = d_ax if len(d_ax) > 1 else d_ax[0]
                else:
                    both = d_ax + ("model",)
                    if S % _axis_size(mesh, both) == 0:
                        spec[2] = both
        elif k == "ssm":
            L, B, H, Pd, N = shape
            if B % _axis_size(mesh, d_ax) == 0 and B > 1:
                spec[1] = d_ax if len(d_ax) > 1 else d_ax[0]
            if H % m == 0:
                spec[2] = "model"
        elif k == "conv":
            L, B, W, C = shape
            if B % _axis_size(mesh, d_ax) == 0 and B > 1:
                spec[1] = d_ax if len(d_ax) > 1 else d_ax[0]
            if C % m == 0:
                spec[3] = "model"
        out[k] = NamedSharding(mesh, P(*spec))
    return out
