"""Client availability / failure simulation (DESIGN.md §11).

The fault-injection layer of the federated round. Three pieces:

1. **A deterministic failure schedule.** Per round, a *fault key* is
   folded out of the round key (``fold_fault_key``); per-client draws
   fold the (static) client index into it (``fault_draws``). The
   schedule — who is offline, who crashes after local training, who
   straggles and by how many rounds — is therefore a pure function of
   (seed, round, client index): the fused ``lax.scan`` driver, the
   per-round loop driver, and ``make_sharded_round`` replay
   bit-identical failure schedules, and every shard of a mesh can
   recompute the full-population schedule REPLICATED (no collective
   moves to agree on who failed).

2. **Fault state that rides the round carry.** ``FaultState`` holds the
   crash-rejoin trace (``offline_until``), and a one-slot-per-client
   staleness buffer for in-flight straggler uploads: the released
   payload (``pending`` — the only parameter-sized piece, shardable
   over the client axis), its arrival round, its weight at send time,
   and the round it was computed (``birth``, for staleness
   discounting). A client with an upload in flight is busy and does not
   start a new round — the realistic straggler trace.

3. **Degraded-mode reductions.** Linear strategies renormalize their
   weights over the survivors; the robust rank-trims shrink their trim
   depth with the *surviving* client count (``masked_robust_reduce``
   computes k from a traced n instead of the static C); a zero-survivor
   round is a no-op on params, ``AggState``, and the EF residual
   (``tree_where`` gates the applied update). Everything is masks and
   ``jnp.where`` — no Python branching inside the jitted round.

EF composition (DESIGN.md §11): a client's EF21 residual row advances
exactly when its compressed delta is *released* — fresh uploads and
straggler sends (they do compress and transmit; the network is what's
slow) advance it at training time; crashed and offline clients never
release, so their rows are untouched.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AvailabilityConfig

PyTree = Any

# fold_in tag deriving the round's fault key from the round key (the §9
# noise-key scheme: one fixed constant, distinct from every other tag /
# split index the round consumes).
_FAULT_TAG = 0xFA117
# empty slot sentinel for the pending-arrival round
NO_PENDING = np.int32(-1)  # numpy: a jnp scalar would start the backend
# denominator floor for survivor-mass renormalization (never divides by
# zero; zero-survivor rounds are where-gated to a no-op anyway)
_MASS_FLOOR = 1e-12


def fold_fault_key(round_key: jnp.ndarray) -> jnp.ndarray:
    """The round's fault key. Folded from the ROUND key (not the
    per-client training keys) so every engine — and every shard — can
    derive the full population's schedule from one replicated value."""
    return jax.random.fold_in(round_key, _FAULT_TAG)


class FaultDraws(NamedTuple):
    """Raw per-client randomness for one round (all (C,))."""

    online: jnp.ndarray  # bool: reachable this round
    crash: jnp.ndarray  # bool: would crash after local train (if online)
    straggle: jnp.ndarray  # bool: would straggle (if online, no crash)
    delay: jnp.ndarray  # int32 in [1, max_staleness]: straggler delay


def fault_draws(fault_key: jnp.ndarray, num_clients: int,
                cfg: AvailabilityConfig) -> FaultDraws:
    """Per-client Bernoulli/delay draws from fold-out keys. Client c's
    draws depend only on (fault_key, c) — subsampling, sharding, and
    engine choice cannot perturb them."""
    hi = max(cfg.max_staleness, 1) + 1

    def one(c):
        k = jax.random.fold_in(fault_key, c)
        u = jax.random.uniform(k, (3,), jnp.float32)
        d = jax.random.randint(jax.random.fold_in(k, 1), (), 1, hi)
        return u, d

    u, delay = jax.vmap(one)(jnp.arange(num_clients, dtype=jnp.int32))
    online = u[:, 0] < cfg.online_prob
    crash = u[:, 1] < cfg.crash_prob
    straggle = jnp.logical_and(u[:, 2] < cfg.straggler_prob,
                               cfg.max_staleness > 0)
    return FaultDraws(online=online, crash=crash, straggle=straggle,
                      delay=delay.astype(jnp.int32))


class FaultState(NamedTuple):
    """Cross-round fault state (rides the scan carry / sharded round
    arguments). ``pending`` is the only parameter-sized leaf — under
    ``make_sharded_round`` it shards over the client axis while every
    other leaf stays replicated (``launch/sharding.py::
    fault_state_shardings``), because the schedule metadata is
    replicated-computable but the payloads live with their clients."""

    round: jnp.ndarray  # () int32: rounds elapsed under this schedule
    offline_until: jnp.ndarray  # (C,) int32: crash-rejoin gate
    pending: jnp.ndarray  # (C, P) f32: in-flight released payloads
    pending_due: jnp.ndarray  # (C,) int32 arrival round; NO_PENDING=empty
    pending_weight: jnp.ndarray  # (C,) f32: raw weight at send time
    pending_birth: jnp.ndarray  # (C,) int32: round the update was made


def init_fault_state(num_clients: int, num_params: int) -> FaultState:
    return FaultState(
        round=jnp.zeros((), jnp.int32),
        offline_until=jnp.zeros((num_clients,), jnp.int32),
        pending=jnp.zeros((num_clients, num_params), jnp.float32),
        pending_due=jnp.full((num_clients,), NO_PENDING, jnp.int32),
        pending_weight=jnp.zeros((num_clients,), jnp.float32),
        pending_birth=jnp.zeros((num_clients,), jnp.int32))


class RoundSchedule(NamedTuple):
    """This round's resolved failure schedule (all (C,) bool except
    ``delay``/``staleness``). Disjoint by construction:
    available = fresh ∪ crashed ∪ straggle."""

    available: jnp.ndarray  # online ∧ rejoined ∧ not busy: trains now
    fresh: jnp.ndarray  # trains AND releases this round
    crashed: jnp.ndarray  # trains, update lost before release
    straggle: jnp.ndarray  # trains, release arrives `delay` rounds late
    arrive: jnp.ndarray  # a buffered upload lands this round
    delay: jnp.ndarray  # (C,) int32 straggler delays
    staleness: jnp.ndarray  # (C,) int32: rounds late, 0 where ~arrive


def round_schedule(fault_key: jnp.ndarray, state: FaultState,
                   cfg: AvailabilityConfig, num_clients: int
                   ) -> RoundSchedule:
    """Resolve the raw draws against the carried fault state."""
    d = fault_draws(fault_key, num_clients, cfg)
    in_flight = jnp.logical_and(state.pending_due >= 0,
                                state.pending_due > state.round)
    rejoined = state.round >= state.offline_until
    available = d.online & rejoined & ~in_flight
    crashed = available & d.crash
    straggle = available & ~d.crash & d.straggle
    fresh = available & ~d.crash & ~d.straggle
    arrive = state.pending_due == state.round
    staleness = jnp.where(arrive, state.round - state.pending_birth, 0)
    return RoundSchedule(available=available, fresh=fresh, crashed=crashed,
                         straggle=straggle, arrive=arrive, delay=d.delay,
                         staleness=staleness.astype(jnp.int32))


def staleness_discount(staleness: jnp.ndarray, power: float) -> jnp.ndarray:
    """Polynomial discount s(τ) = (1 + τ)^(-power) (FedBuff's 1/sqrt at
    power=0.5); τ=0 (fresh) is exactly 1."""
    return (1.0 + staleness.astype(jnp.float32)) ** (-power)


def advance_fault_state(state: FaultState, sched: RoundSchedule,
                        sent: jnp.ndarray, send_weight: jnp.ndarray,
                        rejoin_rounds: int = 0) -> FaultState:
    """Next round's fault state: stragglers' released payloads enter the
    buffer (``sent`` is the full-(C, P) released matrix; only rows where
    ``sched.straggle`` are stored), arrivals clear their slot, crashed
    clients start their rejoin countdown (static ``rejoin_rounds`` extra
    rounds offline after the crashed one)."""
    r = state.round
    strag = sched.straggle
    arr = sched.arrive
    pending = jnp.where(strag[:, None], sent,
                        jnp.where(arr[:, None], 0.0, state.pending))
    due = jnp.where(strag, r + sched.delay,
                    jnp.where(arr, NO_PENDING, state.pending_due))
    weight = jnp.where(strag, send_weight,
                       jnp.where(arr, 0.0, state.pending_weight))
    birth = jnp.where(strag, r, state.pending_birth)
    offline_until = jnp.where(sched.crashed, r + 1 + int(rejoin_rounds),
                              state.offline_until)
    return state._replace(round=r + 1, offline_until=offline_until,
                          pending=pending, pending_due=due,
                          pending_weight=weight, pending_birth=birth)


def tree_where(pred: jnp.ndarray, a: PyTree, b: PyTree) -> PyTree:
    """Leafwise where(pred, a, b) — the zero-survivor no-op gate for
    params and ``AggState`` (pred is a traced scalar bool)."""
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def masked_mean_weights(weights: jnp.ndarray, mask: jnp.ndarray
                        ) -> jnp.ndarray:
    """Linear-family degraded mode: zero non-survivors, renormalize the
    surviving mass. All-zero input stays all-zero (the no-op gate makes
    the round inert regardless)."""
    w = jnp.where(mask, weights.astype(jnp.float32), 0.0)
    return w / jnp.maximum(jnp.sum(w), _MASS_FLOOR)


def masked_robust_reduce_flat(vecs: jnp.ndarray, weights: jnp.ndarray,
                              mask: jnp.ndarray, *, name: str,
                              trim_frac: float = 0.0) -> jnp.ndarray:
    """Rank-trim reduce over the SURVIVING clients of a (C, P) matrix.

    Non-survivors are pushed past the top of every coordinate's ranking
    (+inf sort key) and excluded from the keep window, so the trim depth
    k shrinks with the traced survivor count n: k = min(⌊frac·n⌋,
    ⌊(n−1)/2⌋) for ``trimmed_mean`` — the static-C clamp of
    ``aggregation._trim_k`` applied to the realized n — and
    k = ⌊(n−1)/2⌋ for ``median``. n ≤ 2·k never happens by
    construction; n = 0 returns zeros (callers gate the apply)."""
    x = vecs.astype(jnp.float32)
    c = x.shape[0]
    m = mask.astype(bool)
    n = jnp.sum(m.astype(jnp.int32))
    if name == "median":
        k = jnp.maximum(n - 1, 0) // 2
    elif name == "trimmed_mean":
        k = jnp.minimum(jnp.floor(trim_frac * n.astype(jnp.float32))
                        .astype(jnp.int32), jnp.maximum(n - 1, 0) // 2)
    else:
        raise ValueError(f"no masked robust reduce for strategy {name!r}")
    sort_key = jnp.where(m[:, None], x, jnp.inf)
    order = jnp.argsort(sort_key, axis=0)  # stable; masked rows sink last
    xs = jnp.take_along_axis(x, order, axis=0)
    ws = jnp.where(m, weights.astype(jnp.float32), 0.0)[order]
    ranks = jnp.arange(c, dtype=jnp.int32)[:, None]
    keep = (ranks >= k) & (ranks < n - k)
    num = jnp.sum(jnp.where(keep, ws * xs, 0.0), axis=0)
    den = jnp.sum(jnp.where(keep, ws, 0.0), axis=0)
    return jnp.where(den > 0.0, num / jnp.maximum(den, _MASS_FLOOR), 0.0)
