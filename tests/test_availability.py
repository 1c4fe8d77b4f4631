"""Fault-tolerant asynchronous federation (DESIGN.md §11).

Contracts:

1. degeneracy — the benign ``AvailabilityConfig()`` default disables the
   fault layer *statically*: both drivers trace the exact pre-fault
   computation, BIT-equal to a default run (the privacy/compression
   degeneracy-pin style), and no fault state exists;
2. determinism — the failure schedule is a pure function of
   (seed, round, client index): same seed ⇒ identical schedules,
   survivor counts, and final parameters across the scan and loop
   drivers (bit-equal) and the sharded engine (float-tolerance, the
   tests/test_sharded_fedavg.py convention);
3. degraded modes — weight renormalization over survivors, trim depths
   that shrink with the realized survivor count, and a zero-survivor
   round that is a verified no-op on params, ``AggState``, and the EF
   residual;
4. lifecycle — straggler buffering (busy while in flight, arrival at
   the due round with the right staleness), crash-rejoin gating, and
   EF21 residual rows frozen for clients whose release was lost;
5. composition — fedbuff(buffer_k=1) at full participation degenerates
   to fedavg bit-for-bit; the RDP accountant's sampling rate reflects
   realized participation (availability ∧ sampling); the sharded
   engine's collective schedule keeps the fault-free byte counts
   (pinned via ``lower_gpo_round`` in a forked-device subprocess).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (
    AggConfig,
    AvailabilityConfig,
    CompressionConfig,
    FedConfig,
    GPOConfig,
    PrivacyConfig,
)
from repro.core import (
    FederatedGPO,
    make_aggregator,
    normalize_weights,
)
from repro.core import availability as av
from repro.core.aggregation import trimmed_mean_reduce_flat
from repro.core.federated import make_sharded_round
from repro.core.gpo import init_gpo_params
from repro.core.fedavg import broadcast_to_clients
from repro.data import SurveyConfig, make_survey_data, split_groups
from repro.optim import adam
from repro.utils.pytree import tree_count_params

GCFG = GPOConfig(d_embed=8, d_model=16, num_layers=1, num_heads=2, d_ff=32)

FAULTY = AvailabilityConfig(online_prob=0.7, crash_prob=0.15,
                            straggler_prob=0.3, max_staleness=3,
                            rejoin_rounds=1)


def _make_fed(avail=AvailabilityConfig(), agg=AggConfig(),
              privacy=PrivacyConfig(), compression=CompressionConfig(
                  kind="none", error_feedback=False),
              batch_groups=0, seed=3, rounds=4):
    data = make_survey_data(SurveyConfig(
        num_groups=6, num_questions=24, d_embed=8, seed=seed))
    tr, ev = split_groups(data, seed=seed)
    fcfg = FedConfig(num_clients=len(tr), rounds=rounds, local_epochs=2,
                     eval_every=2, num_context=4, num_target=4,
                     batch_groups=batch_groups, agg=agg, privacy=privacy,
                     compression=compression, avail=avail, seed=seed)
    return FederatedGPO(GCFG, fcfg, data, tr, ev)


# ---------------------------------------------------------------------------
# schedule unit tests (no training)
# ---------------------------------------------------------------------------
def test_schedule_deterministic_and_disjoint():
    cfg = AvailabilityConfig(online_prob=0.6, crash_prob=0.3,
                             straggler_prob=0.4, max_staleness=4)
    fkey = av.fold_fault_key(jax.random.PRNGKey(42))
    state = av.init_fault_state(64, 3)
    s1 = av.round_schedule(fkey, state, cfg, 64)
    s2 = av.round_schedule(fkey, state, cfg, 64)
    for a, b in zip(s1, s2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    fresh, crashed, strag = (np.asarray(s1.fresh), np.asarray(s1.crashed),
                             np.asarray(s1.straggle))
    avail_ = np.asarray(s1.available)
    # disjoint partition of the available set
    assert not (fresh & crashed).any()
    assert not (fresh & strag).any()
    assert not (crashed & strag).any()
    np.testing.assert_array_equal(fresh | crashed | strag, avail_)
    # the probabilities actually bite at C=64
    assert 0 < avail_.sum() < 64 and crashed.any() and strag.any()
    d = np.asarray(s1.delay)
    assert (d >= 1).all() and (d <= 4).all()
    # a different round key reshuffles the schedule
    s3 = av.round_schedule(av.fold_fault_key(jax.random.PRNGKey(43)),
                           state, cfg, 64)
    assert (np.asarray(s3.available) != avail_).any()


def test_straggler_buffer_lifecycle():
    """Send → busy while in flight → arrive with the right staleness →
    slot cleared."""
    cfg = AvailabilityConfig(straggler_prob=0.5, max_staleness=4)
    C, P = 3, 2
    state = av.init_fault_state(C, P)
    t = jnp.array([True, False, False])
    f = jnp.zeros((C,), bool)
    sched = av.RoundSchedule(
        available=t, fresh=~t, crashed=f, straggle=t, arrive=f,
        delay=jnp.full((C,), 2, jnp.int32), staleness=jnp.zeros((C,),
                                                               jnp.int32))
    sent = jnp.arange(C * P, dtype=jnp.float32).reshape(C, P)
    w = jnp.array([0.5, 0.25, 0.25])
    state = av.advance_fault_state(state, sched, sent, w)
    assert int(state.round) == 1
    np.testing.assert_array_equal(np.asarray(state.pending[0]),
                                  np.asarray(sent[0]))
    assert int(state.pending_due[0]) == 2  # sent at r=0, delay 2
    assert float(state.pending_weight[0]) == 0.5
    assert int(state.pending_birth[0]) == 0
    assert int(state.pending_due[1]) == int(av.NO_PENDING)

    # r=1: in flight — busy (not available), not arriving
    fkey = av.fold_fault_key(jax.random.PRNGKey(0))
    s1 = av.round_schedule(fkey, state, cfg, C)
    assert not bool(s1.available[0]) and not bool(s1.arrive[0])

    # r=2: the upload lands, two rounds stale
    state2 = state._replace(round=jnp.asarray(2, jnp.int32))
    s2 = av.round_schedule(fkey, state2, cfg, C)
    assert bool(s2.arrive[0]) and int(s2.staleness[0]) == 2
    state3 = av.advance_fault_state(state2, s2, jnp.zeros((C, P)),
                                    jnp.zeros((C,)))
    assert int(state3.pending_due[0]) == int(av.NO_PENDING)
    assert not np.asarray(state3.pending[0]).any()
    assert float(state3.pending_weight[0]) == 0.0


def test_crash_rejoin_gate():
    cfg = AvailabilityConfig(crash_prob=0.5, rejoin_rounds=2)
    C = 2
    state = av.init_fault_state(C, 1)
    t = jnp.array([True, False])
    f = jnp.zeros((C,), bool)
    z = jnp.zeros((C,), jnp.int32)
    sched = av.RoundSchedule(available=t, fresh=f, crashed=t, straggle=f,
                             arrive=f, delay=z + 1, staleness=z)
    state = av.advance_fault_state(state, sched, jnp.zeros((C, 1)),
                                   jnp.zeros((C,)), cfg.rejoin_rounds)
    # crashed at r=0 with 2 extra rounds offline: back at r=3
    assert int(state.offline_until[0]) == 3
    benign = AvailabilityConfig(online_prob=1.0, crash_prob=0.0)
    fkey = av.fold_fault_key(jax.random.PRNGKey(1))
    for r, avail_expected in ((1, False), (2, False), (3, True)):
        s = av.round_schedule(
            fkey, state._replace(round=jnp.asarray(r, jnp.int32)),
            benign, C)
        assert bool(s.available[0]) == avail_expected


def test_staleness_discount():
    tau = jnp.array([0, 1, 3], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(av.staleness_discount(tau, 0.5)),
        [1.0, 1.0 / np.sqrt(2.0), 0.5], rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(av.staleness_discount(tau, 0.0)), np.ones(3))


def test_masked_mean_weights():
    w = jnp.array([1.0, 2.0, 3.0, 4.0])
    m = jnp.array([True, False, True, False])
    np.testing.assert_allclose(np.asarray(av.masked_mean_weights(w, m)),
                               [0.25, 0.0, 0.75, 0.0], rtol=1e-6)
    zero = av.masked_mean_weights(w, jnp.zeros((4,), bool))
    np.testing.assert_array_equal(np.asarray(zero), np.zeros(4))


def test_normalize_weights_all_zero_sizes_is_finite():
    """Regression: an all-zero size vector — every sampled client lost
    its data, the empty-survivor edge the availability simulator can
    produce — once divided by zero in ``normalize_weights``. The clamped
    denominator returns all-zero weights (a no-op round), and any real
    population is bit-unaffected by the clamp."""
    from repro.core import normalize_weights

    w = normalize_weights(jnp.zeros((4,)))
    assert np.isfinite(np.asarray(w)).all()
    np.testing.assert_array_equal(np.asarray(w), np.zeros(4))
    w2 = normalize_weights(jnp.array([1.0, 3.0]))
    np.testing.assert_allclose(np.asarray(w2), [0.25, 0.75], rtol=1e-6)


@pytest.mark.parametrize("name,frac", [("median", 0.0),
                                       ("trimmed_mean", 0.25)])
def test_masked_robust_reduce_matches_dense_on_survivors(name, frac):
    """The masked rank-trim with a traced survivor count must equal the
    static-C reduce run on the compacted surviving rows."""
    rng = np.random.default_rng(0)
    vecs = jnp.asarray(rng.normal(size=(6, 5)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.5, 1.5, size=6).astype(np.float32))
    mask = jnp.array([True, False, True, True, False, True])
    got = av.masked_robust_reduce_flat(vecs, w, mask, name=name,
                                       trim_frac=frac)
    n = int(mask.sum())
    k = (n - 1) // 2 if name == "median" else min(int(frac * n),
                                                 (n - 1) // 2)
    want = trimmed_mean_reduce_flat(vecs[mask], w[mask], k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-7)


def test_masked_robust_reduce_zero_survivors_is_zero():
    vecs = jnp.ones((4, 3))
    out = av.masked_robust_reduce_flat(vecs, jnp.ones((4,)),
                                       jnp.zeros((4,), bool), name="median")
    np.testing.assert_array_equal(np.asarray(out), np.zeros(3))


def test_availability_config_validation():
    with pytest.raises(ValueError, match="online_prob"):
        AvailabilityConfig(online_prob=1.5).validate()
    with pytest.raises(ValueError, match="max_staleness >= 1"):
        AvailabilityConfig(straggler_prob=0.2).validate()
    FAULTY.validate()  # the canonical faulty config is well-formed


# ---------------------------------------------------------------------------
# degeneracy pin: the disabled default is bit-equal (both drivers)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("engine", ["scan", "loop"])
def test_disabled_faults_is_bit_equal(engine):
    """A benign AvailabilityConfig must not perturb a single bit of the
    default run — the fault layer is statically traced out, and the
    inert knobs (max_staleness, rejoin_rounds) change nothing while
    every probability stays benign."""
    fed_ref = _make_fed()
    hist_ref = fed_ref.run(rounds=3, engine=engine)
    benign = AvailabilityConfig(online_prob=1.0, crash_prob=0.0,
                                straggler_prob=0.0, max_staleness=4,
                                rejoin_rounds=2)
    assert not benign.enabled
    fed = _make_fed(avail=benign)
    hist = fed.run(rounds=3, engine=engine)
    assert hist_ref.round_loss == hist.round_loss  # floats, bit-for-bit
    np.testing.assert_array_equal(np.stack(hist_ref.eval_scores),
                                  np.stack(hist.eval_scores))
    for a, b in zip(jax.tree.leaves(fed_ref.global_params),
                    jax.tree.leaves(fed.global_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert fed.fault_state is None  # no fault state exists when disabled
    assert hist.round_survivors == []


# ---------------------------------------------------------------------------
# deterministic replay across engines
# ---------------------------------------------------------------------------
def test_fault_replay_bit_equal_across_drivers():
    """Same seed ⇒ the same failure schedule, survivor counts, losses,
    parameters, and carried fault state in the scan and loop drivers."""
    runs = {}
    for engine in ("scan", "loop"):
        fed = _make_fed(avail=FAULTY, seed=7)
        hist = fed.run(rounds=6, engine=engine)
        runs[engine] = (fed, hist)
    fed_s, hist_s = runs["scan"]
    fed_l, hist_l = runs["loop"]
    assert hist_s.round_survivors == hist_l.round_survivors
    assert len(hist_s.round_survivors) == 6
    assert hist_s.round_loss == hist_l.round_loss  # bit-for-bit
    for a, b in zip(jax.tree.leaves(fed_s.global_params),
                    jax.tree.leaves(fed_l.global_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(fed_s.fault_state, fed_l.fault_state):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # faults actually fired for this seed (the run is a real fault trace)
    assert min(hist_s.round_survivors) < len(fed_s.train_groups)


def test_fault_replay_with_subsampling_privacy_and_compression():
    """The full stack composes: subsampled cohorts, DP release, int8+EF
    transport, and the failure schedule all replay bit-identically."""
    kw = dict(avail=FAULTY, batch_groups=4, seed=9,
              privacy=PrivacyConfig(clip_norm=1.0, noise_multiplier=0.3),
              compression=CompressionConfig(kind="int8"))
    fed_a = _make_fed(**kw)
    hist_a = fed_a.run(rounds=5, engine="scan")
    fed_b = _make_fed(**kw)
    hist_b = fed_b.run(rounds=5, engine="loop")
    assert hist_a.round_loss == hist_b.round_loss
    assert hist_a.round_survivors == hist_b.round_survivors
    np.testing.assert_array_equal(np.asarray(fed_a.ef_resid),
                                  np.asarray(fed_b.ef_resid))
    for a, b in zip(jax.tree.leaves(fed_a.global_params),
                    jax.tree.leaves(fed_b.global_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# degraded modes
# ---------------------------------------------------------------------------
def test_zero_survivor_rounds_are_noop():
    """online_prob=0: every round has zero survivors and must leave the
    params, the AggState, and the EF residual bit-untouched."""
    avail = AvailabilityConfig(online_prob=0.0)
    fed = _make_fed(avail=avail, agg=AggConfig(name="fedavgm"),
                    compression=CompressionConfig(kind="int8"))
    params0 = [np.array(x) for x in jax.tree.leaves(fed.global_params)]
    srv0 = [np.array(x) for x in jax.tree.leaves(fed.server_state)]
    resid0 = np.array(fed.ef_resid)
    hist = fed.run(rounds=3, engine="scan")
    assert hist.round_survivors == [0, 0, 0]
    for a, b in zip(params0, jax.tree.leaves(fed.global_params)):
        np.testing.assert_array_equal(a, np.asarray(b))
    for a, b in zip(srv0, jax.tree.leaves(fed.server_state)):
        np.testing.assert_array_equal(a, np.asarray(b))
    np.testing.assert_array_equal(resid0, np.asarray(fed.ef_resid))


@pytest.mark.parametrize("name", ["trimmed_mean", "median", "fedbuff"])
def test_faulty_runs_stay_finite_per_strategy(name):
    """Robust and buffered strategies run under heavy faults without
    NaNs and still make progress on the surviving updates."""
    agg = AggConfig(name=name, trim_frac=0.2, buffer_k=2)
    fed = _make_fed(avail=FAULTY, agg=agg, seed=5)
    hist = fed.run(rounds=6, engine="scan")
    assert np.isfinite(np.asarray(hist.round_loss)).all()
    assert all(np.isfinite(s).all() for s in hist.eval_scores)
    assert max(hist.round_survivors) > 0


# ---------------------------------------------------------------------------
# EF-freeze: lost clients' residual rows do not advance
# ---------------------------------------------------------------------------
def test_ef_residual_frozen_for_lost_clients():
    avail = AvailabilityConfig(online_prob=0.8, crash_prob=0.4)

    def first_round_keep(seed, num_clients):
        # host replay of the round's schedule (same key chain as the driver)
        key = jax.random.PRNGKey(seed + 1)
        _, k_round, _ = jax.random.split(key, 3)
        sched = av.round_schedule(av.fold_fault_key(k_round),
                                  av.init_fault_state(num_clients, 1), avail,
                                  num_clients)
        return np.asarray(sched.fresh | sched.straggle)

    # the first seed whose first round both keeps and loses clients (the
    # schedule is a pure function of the seed, but which seed mixes the
    # two cases depends on JAX's PRNG stream)
    C = len(_make_fed().train_groups)
    seed = next(s for s in range(3, 64)
                if 0 < first_round_keep(s, C).sum() < C)
    fed = _make_fed(avail=avail, seed=seed,
                    compression=CompressionConfig(kind="int8"))
    fed.run(rounds=1, engine="loop")
    keep = first_round_keep(seed, C)
    resid = np.asarray(fed.ef_resid)
    row_active = np.abs(resid).max(axis=1) > 0
    # releasing clients accumulated quantization error; lost clients'
    # rows are exactly the zeros they started from
    np.testing.assert_array_equal(row_active, keep)


# ---------------------------------------------------------------------------
# fedbuff degeneracy + accountant composition
# ---------------------------------------------------------------------------
def test_fedbuff_bufferk1_full_participation_is_fedavg():
    fed_avg = _make_fed(agg=AggConfig(name="fedavg"))
    h_avg = fed_avg.run(rounds=4, engine="scan")
    fed_buf = _make_fed(agg=AggConfig(name="fedbuff", buffer_k=1))
    h_buf = fed_buf.run(rounds=4, engine="scan")
    assert h_avg.round_loss == h_buf.round_loss  # bit-for-bit
    for a, b in zip(jax.tree.leaves(fed_avg.global_params),
                    jax.tree.leaves(fed_buf.global_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_accountant_uses_realized_participation():
    assert AvailabilityConfig(online_prob=0.8,
                              crash_prob=0.25).release_rate() == 0.8 * 0.75
    assert AvailabilityConfig().release_rate() == 1.0
    priv = PrivacyConfig(clip_norm=1.0, noise_multiplier=0.8)
    fed_full = _make_fed(privacy=priv, batch_groups=4)
    fed_faulty = _make_fed(privacy=priv, batch_groups=4, avail=FAULTY)
    q_full = fed_full._accountant.sampling_rate
    q_faulty = fed_faulty._accountant.sampling_rate
    np.testing.assert_allclose(q_faulty,
                               q_full * FAULTY.release_rate(), rtol=1e-12)
    # fewer realized releases ⇒ a strictly smaller epsilon
    assert fed_faulty._accountant.epsilon(100) \
        < fed_full._accountant.epsilon(100)


# ---------------------------------------------------------------------------
# sharded engine: same failure trace, same collective schedule
# ---------------------------------------------------------------------------
def test_sharded_fault_round_matches_stacked_engine():
    """Driving make_sharded_round (1-device 'data' mesh) with the loop
    driver's key chain must replay the exact failure schedule and land
    on the same parameters and fault state (float tolerance — the
    tests/test_sharded_fedavg.py convention for separately-compiled
    programs)."""
    C = 4
    data = make_survey_data(SurveyConfig(
        num_groups=C + 1, num_questions=24, d_embed=8, seed=0))
    tr = jnp.arange(C, dtype=jnp.int32)
    ev = jnp.arange(C, C + 1, dtype=jnp.int32)
    fcfg = FedConfig(num_clients=C, rounds=3, local_epochs=2,
                     num_context=4, num_target=4, eval_every=100,
                     avail=FAULTY, seed=11)
    fed = FederatedGPO(GCFG, fcfg, data, tr, ev)
    hist = fed.run(rounds=3, engine="loop")

    mesh = jax.make_mesh((1,), ("data",))
    round_fn = jax.jit(make_sharded_round(GCFG, fcfg, data, mesh,
                                          opt=adam(fcfg.lr)))
    agg = make_aggregator(fcfg.agg, num_clients=C)
    params = init_gpo_params(GCFG, jax.random.PRNGKey(fcfg.seed))
    cp = broadcast_to_clients(params, C)
    opt_states = jax.vmap(adam(fcfg.lr).init)(cp)
    srv = agg.init(params)
    fault = av.init_fault_state(C, tree_count_params(params))
    weights = normalize_weights(data.sizes[tr])
    key = jax.random.PRNGKey(fcfg.seed + 1)
    for _ in range(3):
        key, k_round, _ = jax.random.split(key, 3)
        _, k_train = jax.random.split(k_round)
        keys = jax.random.split(k_train, C)
        fkey = av.fold_fault_key(k_round)
        cp, opt_states, _, srv, fault = round_fn(
            cp, opt_states, keys, tr, weights, srv, fault, fkey)
    # identical integer fault trace, same params to float tolerance
    for a, b in zip(fed.fault_state, fault):
        if np.asarray(a).dtype.kind == "i":
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(fed.global_params),
                    jax.tree.leaves(cp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b)[0],
                                   rtol=1e-5, atol=1e-6)
    assert min(hist.round_survivors) < C  # the trace exercised faults


@pytest.mark.slow
def test_sharded_fault_round_keeps_collective_bytes():
    """Masking survivors must not change the wire: the fault-aware
    linear round compiles to the SAME single parameter-sized all-reduce
    (byte-identical) as the fault-free round. Runs in a subprocess — the
    8-device host-platform override is process-global."""
    code = """
import json
from repro.launch.dryrun import lower_gpo_round
out = {}
for faults in (False, True):
    r = lower_gpo_round("fedavg", clients=8, faults=faults, verbose=False)
    out[str(faults)] = r["collective_bytes_by_kind"]
print(json.dumps(out))
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + sys.path))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["True"] == out["False"]
    assert out["True"].get("all-reduce", 0) > 0
