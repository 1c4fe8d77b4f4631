"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).

Every mesh here has ``Auto`` axes: ``jax.make_mesh`` defaults to
``Explicit`` axes, which ``with_sharding_constraint`` (and so
``models.partitioning.shard_act``) refuses.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types (module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e: one pod = 16x16 = 256 chips (data, model);
    multi-pod = 2 pods = 512 chips with a leading hierarchical 'pod' axis
    (DCI-connected) carrying hierarchical FedAvg / data parallelism."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    return make_mesh((data, model), ("data", "model"))


def make_edge_mesh(num_edges: int, clients_per_edge: int):
    """Two-level federation mesh (DESIGN.md §14): a leading 'edge' axis
    of E edge shards in front of the intra-edge client ('data') axis.
    Hand ``client_axes=('edge', 'data')`` to ``make_sharded_round`` with
    ``FedConfig.hierarchy.num_edges == num_edges`` and the robust
    family's aggregate stage compiles the real two-hop collective
    schedule: an intra-edge all-gather of C/E rows, then a cross-edge
    all-gather of only E candidate rows (int8 when the §10 codec is on).
    The linear family keeps its single psum over both axes — which IS
    the composed two-hop partial-sum schedule on a real torus."""
    return make_mesh((num_edges, clients_per_edge), ("edge", "data"))


def data_axes(mesh) -> tuple[str, ...]:
    """The batch/client axes: ('pod', 'data') on multi-pod, else ('data',)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def client_axes(mesh) -> tuple[str, ...]:
    """The federated CLIENT axes, in hop order: the hierarchical outer
    axis first ('edge' on a §14 edge mesh, 'pod' multi-pod), then the
    intra-shard 'data' axis."""
    return tuple(a for a in mesh.axis_names if a in ("edge", "pod", "data"))


def model_axis_size(mesh) -> int:
    return mesh.shape["model"]
