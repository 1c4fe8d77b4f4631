"""Pluggable server aggregation — one algorithm becomes a family
(DESIGN.md §7).

The delta contract: every round, client g trains locally from the
broadcast global model and ships the *delta* d_g = theta_g - theta^t.
The server forms a weighted moment of the deltas (or a robust
order-statistic of them) and applies a stateful update:

    Delta^t   = reduce_g(w_g, d_g)                  (reduce)
    theta^t+1 = theta^t + server_update(Delta^t)    (apply)

Plain FedAvg is the degenerate member (weighted-mean reduce, identity
server update with lr 1): theta + sum_g w_g (theta_g - theta) ==
sum_g w_g theta_g, Eq. 3 exactly (up to float reassociation, since the
weights are normalized). Everything the registry adds — FedAvgM server
momentum, FedAdam/FedYogi server moments (Reddi et al. 2021), the
rank-trimmed mean / coordinate-wise median robust reduces (Yin et al.
2018), APPA-style fairness-adaptive group weights — lives behind the
same three-callable contract, so both ``FederatedGPO`` drivers, the
``shard_map`` production round, and the backbone/LoRA trainers consume
any strategy unchanged:

* ``init(global_params) -> AggState`` — server-side state (momentum /
  moment trees, adaptive per-group scores). The state is a plain pytree:
  it rides in the fused scan carry, replicates across mesh shards, and
  checkpoints like parameters.
* ``weigh(state, weights, idx) -> weights`` — per-round weight
  transform; identity except for ``adaptive``.
* ``reduce(deltas, weights) -> delta`` / ``reduce_flat`` — contraction
  over the client axis. ``linear`` strategies are a weighted sum (under
  ``shard_map`` this is ONE weighted psum; with
  ``use_pallas_aggregation`` the Pallas delta-moment kernel); robust
  strategies rank per coordinate (the Pallas sort/trim kernel).
* ``apply(state, global_params, delta, losses, idx)`` — the stateful
  server update; deterministic given the reduced delta, so under
  ``shard_map`` every shard computes it redundantly on the replicated
  psum output (no second collective).

``step`` composes weigh -> reduce -> apply for the client-stacked
engines; the sharded engine calls the pieces around its collectives.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AggConfig
from repro.core.fedavg import fedavg_stacked
from repro.kernels import (
    agg_momentum_reduce,
    agg_pairwise_dists,
    agg_trimmed_reduce,
    fedavg_reduce,
)
from repro.utils.registry import Registry
from repro.utils.pytree import (
    tree_flatten_to_vector,
    tree_index,
    tree_ravel_clients,
    tree_unflatten_from_vector,
)

PyTree = Any

AGGREGATORS: Registry = Registry("aggregator")


class AggState(NamedTuple):
    """Server-side aggregator state (uniform across strategies so every
    engine carries one structure; unused slots are scalar zeros)."""

    step: jnp.ndarray  # rounds aggregated so far
    m: PyTree  # momentum / first-moment tree (fedavgm, fedadam, fedyogi)
    v: PyTree  # second-moment tree (fedadam, fedyogi)
    scores: PyTree  # adaptive: {"ema", "seen"} (num_clients,) arrays; else 0


@dataclass(frozen=True)
class ServerAggregator:
    """(init, weigh, reduce, apply) over parameter-delta pytrees."""

    name: str
    cfg: AggConfig
    linear: bool  # weighted-sum reduce (ONE psum) vs order-statistic
    needs_losses: bool  # apply consumes per-client losses (adaptive)
    init: Callable[[PyTree], AggState]
    weigh: Callable  # (state, weights, idx) -> weights
    reduce: Callable  # (stacked_deltas, weights) -> delta
    reduce_flat: Callable  # ((C, P), (C,)) -> (P,)  [sharded/kernel form]
    apply: Callable  # (state, global, delta, losses, idx) -> (global, state)
    step: Optional[Callable] = None  # weigh+reduce+apply; set in __post_init__
    # buffered strategies (fedbuff) defer the server step until enough
    # released updates accumulate; the fault-aware round path feeds their
    # apply the realized mass/released counts (DESIGN.md §11)
    buffered: bool = False

    def __post_init__(self):
        if self.step is None:
            def step(state, global_params, deltas, weights, losses=None,
                     idx=None, **kw):
                w = self.weigh(state, weights, idx)
                delta = self.reduce(deltas, w)
                return self.apply(state, global_params, delta,
                                  losses=losses, idx=idx, **kw)

            object.__setattr__(self, "step", step)


def make_aggregator(cfg: AggConfig, *, num_clients: int,
                    use_pallas: bool = False) -> ServerAggregator:
    """Build the configured strategy. ``use_pallas`` routes the client-
    axis reductions through the kernels in ``kernels/agg_reduce.py``."""
    builder = AGGREGATORS.get(cfg.name)
    return builder(cfg, num_clients=num_clients, use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------
def _zeros_state(global_params: PyTree, *, with_m=False,
                 with_v=False) -> AggState:
    zt = lambda: jax.tree.map(  # noqa: E731
        lambda p: jnp.zeros_like(p, dtype=jnp.float32), global_params)
    zero = jnp.zeros((), jnp.float32)
    return AggState(
        step=jnp.zeros((), jnp.int32),
        m=zt() if with_m else zero,
        v=zt() if with_v else zero,
        scores=zero)


def _identity_weigh(state, weights, idx):
    return weights


def _linear_reduce(use_pallas: bool):
    """Weighted delta moment: per-leaf jnp contraction, or the Pallas
    reduction on the raveled (C, P) matrix."""
    if not use_pallas:
        return fedavg_stacked, _flat_weighted_mean

    def reduce(deltas, weights):
        like = tree_index(deltas, 0)
        vecs = tree_ravel_clients(deltas)
        return tree_unflatten_from_vector(
            fedavg_reduce(vecs, weights.astype(jnp.float32)), like)

    def reduce_flat(vecs, weights):
        return fedavg_reduce(vecs, weights.astype(jnp.float32))

    return reduce, reduce_flat


def _flat_weighted_mean(vecs, weights):
    return jnp.einsum("c,cp->p", weights.astype(jnp.float32),
                      vecs.astype(jnp.float32))


def _trim_k(c: int, frac: float) -> int:
    """floor(frac*C), clamped so at least one client survives."""
    return min(int(frac * c), (c - 1) // 2)


def trimmed_mean_reduce_flat(vecs: jnp.ndarray, weights: jnp.ndarray,
                             k: int) -> jnp.ndarray:
    """Pure-jnp rank-trimmed weighted mean on (C, P): stable argsort per
    coordinate, drop k at each end, weighted mean of the survivors with
    weights renormalized. k=0 short-circuits to the exact weighted mean
    (no renormalizing division)."""
    if k == 0:
        return _flat_weighted_mean(vecs, weights)
    x = vecs.astype(jnp.float32)
    c = x.shape[0]
    order = jnp.argsort(x, axis=0)  # jnp argsort is stable
    xs = jnp.take_along_axis(x, order, axis=0)
    ws = weights.astype(jnp.float32)[order]
    keep = ((jnp.arange(c) >= k) & (jnp.arange(c) < c - k))
    keep = keep.astype(jnp.float32)[:, None]
    return jnp.sum(keep * ws * xs, axis=0) / jnp.sum(keep * ws, axis=0)


def _robust_reduce(use_pallas: bool, k_of: Callable[[int], int]):
    """Rank-trim reduce; ``k_of(C)`` maps the (static) client count to
    the trim depth, so partial-participation rounds trim consistently."""

    def reduce_flat(vecs, weights):
        k = k_of(vecs.shape[0])
        if use_pallas and k > 0:
            return agg_trimmed_reduce(vecs, weights.astype(jnp.float32),
                                      trim=k)
        return trimmed_mean_reduce_flat(vecs, weights, k)

    def reduce(deltas, weights):
        like = tree_index(deltas, 0)
        vecs = tree_ravel_clients(deltas)
        return tree_unflatten_from_vector(reduce_flat(vecs, weights), like)

    return reduce, reduce_flat


def _apply_sgd(cfg: AggConfig):
    """theta += server_lr * Delta (FedAvg and the robust strategies)."""

    def apply(state: AggState, global_params, delta, losses=None, idx=None,
              **kw):
        new_g = jax.tree.map(
            lambda g, d: (g.astype(jnp.float32)
                          + cfg.server_lr * d.astype(jnp.float32)
                          ).astype(g.dtype), global_params, delta)
        return new_g, state._replace(step=state.step + 1)

    return apply


# ---------------------------------------------------------------------------
# registry entries. Each builder returns a ServerAggregator; the registry
# stores zero-arg factories (utils/registry.py contract) yielding them.
# ---------------------------------------------------------------------------
def _make_fedavg(cfg, *, num_clients, use_pallas):
    reduce, reduce_flat = _linear_reduce(use_pallas)
    return ServerAggregator(
        name=cfg.name, cfg=cfg, linear=True, needs_losses=False,
        init=lambda g: _zeros_state(g),
        weigh=_identity_weigh, reduce=reduce, reduce_flat=reduce_flat,
        apply=_apply_sgd(cfg))


@AGGREGATORS.register("fedavg")
def _fedavg_factory():
    return _make_fedavg


# fedprox: the proximal term is client-side — FedConfig.agg.prox_mu must
# be set > 0 and feeds the mu-regularizer in federated._make_local_train
# (the GPO engine; the backbone/LoRA trainers reject prox_mu > 0). The
# server rule is FedAvg; the name is registered so configs read as the
# recipe they run.
@AGGREGATORS.register("fedprox")
def _fedprox_factory():
    return _make_fedavg


def _make_fedavgm(cfg, *, num_clients, use_pallas):
    reduce, reduce_flat = _linear_reduce(use_pallas)
    beta = cfg.momentum

    def apply(state: AggState, global_params, delta, losses=None, idx=None,
              **kw):
        new_m = jax.tree.map(
            lambda m, d: beta * m + d.astype(jnp.float32), state.m, delta)
        new_g = jax.tree.map(
            lambda g, m: (g.astype(jnp.float32) + cfg.server_lr * m
                          ).astype(g.dtype), global_params, new_m)
        return new_g, state._replace(step=state.step + 1, m=new_m)

    step = None
    if use_pallas:
        # fused path: the delta-moment kernel emits (Delta, beta*m+Delta)
        # in one pass over the client stream (kernels/agg_reduce.py)
        def step(state, global_params, deltas, weights, losses=None,
                 idx=None, **kw):
            vecs = tree_ravel_clients(deltas)
            m_vec = tree_flatten_to_vector(state.m)
            _, nm_vec = agg_momentum_reduce(
                vecs, weights.astype(jnp.float32), m_vec, beta=beta)
            new_m = tree_unflatten_from_vector(nm_vec, state.m)
            new_g = jax.tree.map(
                lambda g, m: (g.astype(jnp.float32) + cfg.server_lr * m
                              ).astype(g.dtype), global_params, new_m)
            return new_g, state._replace(step=state.step + 1, m=new_m)

    return ServerAggregator(
        name=cfg.name, cfg=cfg, linear=True, needs_losses=False,
        init=lambda g: _zeros_state(g, with_m=True),
        weigh=_identity_weigh, reduce=reduce, reduce_flat=reduce_flat,
        apply=apply, step=step)


@AGGREGATORS.register("fedavgm")
def _fedavgm_factory():
    return _make_fedavgm


def _make_fedadaptive(yogi: bool):
    """FedAdam / FedYogi (Reddi et al. 2021): server Adam on the delta."""

    def make(cfg, *, num_clients, use_pallas):
        reduce, reduce_flat = _linear_reduce(use_pallas)
        b1, b2, tau = cfg.beta1, cfg.beta2, cfg.tau

        def apply(state: AggState, global_params, delta, losses=None,
                  idx=None, **kw):
            new_m = jax.tree.map(
                lambda m, d: b1 * m + (1 - b1) * d.astype(jnp.float32),
                state.m, delta)
            if yogi:
                new_v = jax.tree.map(
                    lambda v, d: v - (1 - b2) * jnp.square(
                        d.astype(jnp.float32)) * jnp.sign(
                        v - jnp.square(d.astype(jnp.float32))),
                    state.v, delta)
            else:
                new_v = jax.tree.map(
                    lambda v, d: b2 * v
                    + (1 - b2) * jnp.square(d.astype(jnp.float32)),
                    state.v, delta)
            new_g = jax.tree.map(
                lambda g, m, v: (g.astype(jnp.float32) + cfg.server_lr * m
                                 / (jnp.sqrt(v) + tau)).astype(g.dtype),
                global_params, new_m, new_v)
            return new_g, state._replace(step=state.step + 1, m=new_m,
                                         v=new_v)

        return ServerAggregator(
            name=cfg.name, cfg=cfg, linear=True, needs_losses=False,
            init=lambda g: _zeros_state(g, with_m=True, with_v=True),
            weigh=_identity_weigh, reduce=reduce, reduce_flat=reduce_flat,
            apply=apply)

    return make


@AGGREGATORS.register("fedadam")
def _fedadam_factory():
    return _make_fedadaptive(yogi=False)


@AGGREGATORS.register("fedyogi")
def _fedyogi_factory():
    return _make_fedadaptive(yogi=True)


def _make_trimmed(cfg, *, num_clients, use_pallas):
    reduce, reduce_flat = _robust_reduce(
        use_pallas, lambda c: _trim_k(c, cfg.trim_frac))
    return ServerAggregator(
        name=cfg.name, cfg=cfg, linear=False, needs_losses=False,
        init=lambda g: _zeros_state(g),
        weigh=_identity_weigh, reduce=reduce, reduce_flat=reduce_flat,
        apply=_apply_sgd(cfg))


@AGGREGATORS.register("trimmed_mean")
def _trimmed_factory():
    return _make_trimmed


def _make_median(cfg, *, num_clients, use_pallas):
    reduce, reduce_flat = _robust_reduce(use_pallas, lambda c: (c - 1) // 2)
    return ServerAggregator(
        name=cfg.name, cfg=cfg, linear=False, needs_losses=False,
        init=lambda g: _zeros_state(g),
        weigh=_identity_weigh, reduce=reduce, reduce_flat=reduce_flat,
        apply=_apply_sgd(cfg))


@AGGREGATORS.register("median")
def _median_factory():
    return _make_median


def _make_adaptive(cfg, *, num_clients, use_pallas):
    """APPA-style adaptive per-group weights: groups whose local loss EMA
    sits above the mean get upweighted (temperature fair_temp), pushing
    the fairness index (Eq. 5-6) up; scores update from this round's
    per-client losses. The ``scores`` slot tracks per-client (ema, seen):
    a client's first observation SEEDS its EMA, and clients never sampled
    yet (partial participation) are treated as sitting at the observed
    mean — never down-weighted merely for not having been sampled."""
    reduce, reduce_flat = _linear_reduce(use_pallas)
    temp, decay = cfg.fair_temp, cfg.fair_decay
    base_apply = _apply_sgd(cfg)

    def weigh(state: AggState, weights, idx):
        if temp == 0.0:
            return weights  # exact dataset-size weights (fedavg)
        ema, seen = state.scores["ema"], state.scores["seen"]
        mean_seen = jnp.sum(ema * seen) / jnp.maximum(jnp.sum(seen), 1.0)
        s_full = jnp.where(seen > 0, ema, mean_seen)
        s = s_full if idx is None else s_full[idx]
        w = weights * jnp.exp(temp * (s - jnp.mean(s)))
        return w / jnp.sum(w)

    def apply(state: AggState, global_params, delta, losses=None, idx=None,
              mask=None, **kw):
        new_g, state = base_apply(state, global_params, delta)
        if losses is not None:
            losses = losses.astype(jnp.float32)
            if idx is None:
                idx = jnp.arange(losses.shape[0])
            ema, seen = state.scores["ema"], state.scores["seen"]
            new_ema = jnp.where(seen[idx] > 0,
                                decay * ema[idx] + (1 - decay) * losses,
                                losses)
            new_seen = jnp.ones_like(seen[idx])
            if mask is not None:
                # fault mode: only clients whose update was RELEASED this
                # round observed a trustworthy loss — crashed/offline rows
                # keep their previous score (DESIGN.md §11)
                new_ema = jnp.where(mask, new_ema, ema[idx])
                new_seen = jnp.where(mask, 1.0, seen[idx])
            state = state._replace(scores={
                "ema": ema.at[idx].set(new_ema),
                "seen": seen.at[idx].set(new_seen)})
        return new_g, state

    def init(global_params):
        state = _zeros_state(global_params)
        return state._replace(scores={
            "ema": jnp.zeros((num_clients,), jnp.float32),
            "seen": jnp.zeros((num_clients,), jnp.float32)})

    return ServerAggregator(
        name=cfg.name, cfg=cfg, linear=True, needs_losses=True,
        init=init, weigh=weigh, reduce=reduce, reduce_flat=reduce_flat,
        apply=apply)


@AGGREGATORS.register("adaptive")
def _adaptive_factory():
    return _make_adaptive


def _make_fedbuff(cfg, *, num_clients, use_pallas):
    """FedBuff-style staleness-aware buffered aggregation (Nguyen et al.
    2022; DESIGN.md §11). The reduce is the same ONE-psum weighted delta
    moment as fedavg; the server step is deferred: the reduced update
    accumulates into a buffer (``AggState.m``) together with its weight
    mass and released-client count (``AggState.scores``), and the server
    applies  theta += server_lr * buffer / mass  only once at least
    ``buffer_k`` client updates have been absorbed since the last flush.

    Staleness discounting happens UPSTREAM in the fault-aware round
    (stale arrivals' weights are scaled by (1+tau)^-staleness_power
    before the reduce); this apply only needs the realized ``mass`` and
    ``released`` count. The synchronous engines pass neither: the
    defaults (mass=1, released=|participants|) make buffer_k <= C flush
    every round — fedbuff with buffer_k=1 is bit-for-bit fedavg there."""
    reduce, reduce_flat = _linear_reduce(use_pallas)
    base_lr = cfg.server_lr
    buffer_k = cfg.buffer_k

    def init(global_params):
        state = _zeros_state(global_params, with_m=True)
        return state._replace(scores={
            "count": jnp.zeros((), jnp.float32),
            "mass": jnp.zeros((), jnp.float32)})

    def apply(state: AggState, global_params, delta, losses=None, idx=None,
              mass=None, released=None, **kw):
        if mass is None:
            mass = jnp.ones((), jnp.float32)  # weights pre-normalized
        if released is None:
            released = jnp.asarray(
                idx.shape[0] if idx is not None else num_clients,
                jnp.float32)
        mass = jnp.asarray(mass, jnp.float32)
        released = jnp.asarray(released, jnp.float32)
        buf = jax.tree.map(
            lambda m, d: m + mass * d.astype(jnp.float32), state.m, delta)
        count = state.scores["count"] + released
        total = state.scores["mass"] + mass
        flush = count >= buffer_k
        scale = jnp.where(flush, base_lr / jnp.maximum(total, 1e-12), 0.0)
        new_g = jax.tree.map(
            lambda g, b: (g.astype(jnp.float32) + scale * b
                          ).astype(g.dtype), global_params, buf)
        new_m = jax.tree.map(lambda b: jnp.where(flush, 0.0, b), buf)
        new_scores = {"count": jnp.where(flush, 0.0, count),
                      "mass": jnp.where(flush, 0.0, total)}
        return new_g, state._replace(step=state.step + 1, m=new_m,
                                     scores=new_scores)

    return ServerAggregator(
        name=cfg.name, cfg=cfg, linear=True, needs_losses=False,
        init=init, weigh=_identity_weigh, reduce=reduce,
        reduce_flat=reduce_flat, apply=apply, buffered=True)


@AGGREGATORS.register("fedbuff")
def _fedbuff_factory():
    return _make_fedbuff


# ---------------------------------------------------------------------------
# Byzantine-robust defenses (DESIGN.md §13). All are mask-tolerant via
# the weights vector: rows with weight 0 (crashed / buffered clients in
# the fault-aware round) are excluded from selection and never chosen.
# ---------------------------------------------------------------------------
# finite sentinel for masked pairwise distances / scores. NOT inf: with
# very few active clients every score would be inf and argmin over
# all-inf is a degenerate tie; a large-but-finite sentinel keeps the
# ordering (active < inactive) strict and the arithmetic NaN-free.
_BIG = np.float32(1e30)  # numpy: a jnp scalar would start the backend at import


def _pairwise_sq_dists(vecs: jnp.ndarray, use_pallas: bool) -> jnp.ndarray:
    if use_pallas:
        return agg_pairwise_dists(vecs)
    x = vecs.astype(jnp.float32)
    sq = jnp.sum(x * x, axis=1)
    return jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * x @ x.T, 0.0)


def krum_scores(vecs: jnp.ndarray, weights: jnp.ndarray, f: int, *,
                use_pallas: bool = False) -> jnp.ndarray:
    """(C,) Krum scores (Blanchard et al. 2017): client c's score is the
    sum of its n − f − 2 smallest squared distances to OTHER active
    clients (n = number of active rows). Lower is better — an attacker
    far from the honest cluster accumulates huge distances. ``weights``
    only gates activity here (weight 0 ⇒ excluded from both scoring and
    selection); magnitudes don't shift the order statistics."""
    x = vecs.astype(jnp.float32)
    c = x.shape[0]
    active = weights.astype(jnp.float32) > 0.0
    n = jnp.sum(active.astype(jnp.int32))
    d = _pairwise_sq_dists(x, use_pallas)
    pair_ok = active[:, None] & active[None, :]
    off_diag = ~jnp.eye(c, dtype=bool)
    d = jnp.where(pair_ok & off_diag, d, _BIG)
    # n is traced (fault rounds mask rows dynamically), so the neighbor
    # count is a traced clamp, applied as a rank predicate on the sorted
    # distance rows rather than a static slice.
    nn = jnp.clip(n - f - 2, 1, c - 1)
    ds = jnp.sort(d, axis=1)
    ranks = jnp.arange(c)[None, :]
    score = jnp.sum(jnp.where(ranks < nn, ds, 0.0), axis=1)
    return jnp.where(active, score, _BIG * jnp.float32(c))


def _make_krum(multi: bool):
    def make(cfg, *, num_clients, use_pallas):
        f = cfg.num_malicious
        m_sel = max(1, min(cfg.multi_krum_m, num_clients))

        def reduce_flat(vecs, weights):
            x = vecs.astype(jnp.float32)
            scores = krum_scores(x, weights, f, use_pallas=use_pallas)
            if not multi:
                return x[jnp.argmin(scores)]
            # multi-Krum: weighted mean of the m_sel best-scored rows
            # (weights renormalized over the selection; zero-weight rows
            # may enter the selection set but contribute 0 mass)
            rank = jnp.argsort(jnp.argsort(scores))
            sel = rank < min(m_sel, x.shape[0])
            w = jnp.where(sel, weights.astype(jnp.float32), 0.0)
            w = w / jnp.maximum(jnp.sum(w), 1e-12)
            return jnp.einsum("c,cp->p", w, x)

        def reduce(deltas, weights):
            like = tree_index(deltas, 0)
            return tree_unflatten_from_vector(
                reduce_flat(tree_ravel_clients(deltas), weights), like)

        return ServerAggregator(
            name=cfg.name, cfg=cfg, linear=False, needs_losses=False,
            init=lambda g: _zeros_state(g),
            weigh=_identity_weigh, reduce=reduce, reduce_flat=reduce_flat,
            apply=_apply_sgd(cfg))

    return make


@AGGREGATORS.register("krum")
def _krum_factory():
    return _make_krum(multi=False)


@AGGREGATORS.register("multi_krum")
def _multi_krum_factory():
    return _make_krum(multi=True)


def geometric_median_flat(vecs: jnp.ndarray, weights: jnp.ndarray, *,
                          iters: int, eps: float) -> jnp.ndarray:
    """Smoothed Weiszfeld iteration for the weighted geometric median
    (Pillutla et al. 2022): y ← Σ_c (w_c/max(‖x_c−y‖, eps)) x_c /
    Σ_c (w_c/max(‖x_c−y‖, eps)), a FIXED ``iters`` steps from the
    weighted mean — fixed so the computation is jit-stable (no traced
    convergence test) and every engine runs the identical schedule.
    Zero-weight rows drop out exactly (w_c = 0 ⇒ zero Weiszfeld mass).
    Breakdown point 1/2: any minority weight mass moves the optimum a
    bounded distance, no matter how far the corrupt rows sit."""
    x = vecs.astype(jnp.float32)
    w = jnp.maximum(weights.astype(jnp.float32), 0.0)
    wn = w / jnp.maximum(jnp.sum(w), 1e-12)
    y0 = jnp.einsum("c,cp->p", wn, x)

    def body(_, y):
        dist = jnp.sqrt(jnp.sum(jnp.square(x - y[None, :]), axis=1))
        inv = w / jnp.maximum(dist, eps)
        return (jnp.einsum("c,cp->p", inv, x)
                / jnp.maximum(jnp.sum(inv), 1e-12))

    return jax.lax.fori_loop(0, iters, body, y0)


def _make_geomedian(cfg, *, num_clients, use_pallas):
    iters, eps = cfg.geomedian_iters, cfg.geomedian_eps

    def reduce_flat(vecs, weights):
        return geometric_median_flat(vecs, weights, iters=iters, eps=eps)

    def reduce(deltas, weights):
        like = tree_index(deltas, 0)
        return tree_unflatten_from_vector(
            reduce_flat(tree_ravel_clients(deltas), weights), like)

    return ServerAggregator(
        name=cfg.name, cfg=cfg, linear=False, needs_losses=False,
        init=lambda g: _zeros_state(g),
        weigh=_identity_weigh, reduce=reduce, reduce_flat=reduce_flat,
        apply=_apply_sgd(cfg))


@AGGREGATORS.register("geomedian")
def _geomedian_factory():
    return _make_geomedian
