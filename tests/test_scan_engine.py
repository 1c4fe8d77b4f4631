"""Fused scan round driver == per-round loop driver, and the Pallas
aggregation path == the jnp weighted sum, over multi-round runs.

The two drivers share one round_step and derive RNG keys identically, so
their History metrics and final parameters must agree to float
tolerance (they differ only in how XLA schedules the same ops).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import AggConfig, FedConfig, GPOConfig
from repro.core import FederatedGPO, fedavg_stacked, make_aggregator
from repro.core.federated import make_sharded_round, _make_local_train
from repro.core.fedavg import broadcast_to_clients, normalize_weights
from repro.core.gpo import init_gpo_params
from repro.data import SurveyConfig, make_survey_data, split_groups
from repro.optim import adam

GCFG = GPOConfig(d_embed=24, d_model=48, num_layers=2, num_heads=4, d_ff=96)


def _make_fed(batch_groups=0, use_pallas_aggregation=False, seed=5,
              agg=AggConfig(), use_pallas_attention=None):
    data = make_survey_data(SurveyConfig(
        num_groups=8, num_questions=40, d_embed=24, seed=seed))
    tr, ev = split_groups(data, seed=seed)
    fcfg = FedConfig(num_clients=len(tr), rounds=4, local_epochs=2,
                     eval_every=2, num_context=6, num_target=6,
                     batch_groups=batch_groups,
                     use_pallas_aggregation=use_pallas_aggregation,
                     use_pallas_attention=use_pallas_attention,
                     agg=agg, seed=seed)
    return FederatedGPO(GCFG, fcfg, data, tr, ev)


def _assert_hist_close(ha, hb, tol=dict(rtol=2e-4, atol=1e-5)):
    np.testing.assert_allclose(ha.round_loss, hb.round_loss, **tol)
    assert ha.eval_rounds == hb.eval_rounds
    np.testing.assert_allclose(np.stack(ha.eval_scores),
                               np.stack(hb.eval_scores), rtol=2e-4,
                               atol=1e-4)
    np.testing.assert_allclose(ha.eval_mean_as, hb.eval_mean_as,
                               rtol=2e-4, atol=1e-4)
    np.testing.assert_allclose(ha.eval_fi, hb.eval_fi, rtol=2e-4, atol=1e-4)


@pytest.mark.parametrize("batch_groups", [0, 3],
                         ids=["full_participation", "partial_participation"])
def test_scan_engine_matches_loop(batch_groups):
    fed_loop = _make_fed(batch_groups)
    hist_loop = fed_loop.run(rounds=4, engine="loop")
    fed_scan = _make_fed(batch_groups)
    hist_scan = fed_scan.run(rounds=4, engine="scan")

    _assert_hist_close(hist_loop, hist_scan)
    for a, b in zip(jax.tree.leaves(fed_loop.global_params),
                    jax.tree.leaves(fed_scan.global_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-5)
    # optimizer states advanced identically too (donated buffers returned)
    for a, b in zip(jax.tree.leaves(fed_loop.opt_states),
                    jax.tree.leaves(fed_scan.opt_states)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-5)


def test_scan_engine_chunked_logging_matches_single_block(capsys):
    """log_every chunks the scan into blocks; the RNG chain threads
    through the carried key, so metrics must equal the one-block run."""
    hist_one = _make_fed().run(rounds=4)
    hist_chunked = _make_fed().run(rounds=4, log_every=2)
    _assert_hist_close(hist_one, hist_chunked)
    assert "[fed] round" in capsys.readouterr().out  # logging still live


def test_scan_engine_chunk_remainder_matches_single_block():
    """rounds not divisible by log_every: the tail runs per-round on the
    same key chain instead of recompiling the fused block."""
    fed_one = _make_fed()
    hist_one = fed_one.run(rounds=3)
    fed_rem = _make_fed()
    hist_rem = fed_rem.run(rounds=3, log_every=2)  # chunk of 2 + tail of 1
    _assert_hist_close(hist_one, hist_rem)
    for a, b in zip(jax.tree.leaves(fed_one.global_params),
                    jax.tree.leaves(fed_rem.global_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-5)


def test_run_zero_rounds_returns_empty_history():
    """FedConfig(rounds=0) + run() must return an empty History (the
    pre-scan loop driver's behavior), not crash building the eval mask."""
    data = make_survey_data(SurveyConfig(
        num_groups=8, num_questions=40, d_embed=24, seed=5))
    tr, ev = split_groups(data, seed=5)
    fcfg = FedConfig(num_clients=len(tr), rounds=0, local_epochs=1,
                     num_context=6, num_target=6)
    fed = FederatedGPO(GCFG, fcfg, data, tr, ev)
    for engine in ("scan", "loop"):
        hist = fed.run(engine=engine)
        assert hist.round_loss == [] and hist.eval_rounds == []


def test_scan_engine_is_default_and_resumable():
    fed = _make_fed()
    hist1 = fed.run(rounds=3)  # run(engine="scan") is the default
    assert len(hist1.round_loss) == 3
    assert hist1.eval_rounds == [0, 2]
    # a second block continues from the advanced state without error
    hist2 = fed.run(rounds=3)
    assert len(hist2.round_loss) == 3
    assert np.mean(hist2.round_loss) < np.mean(hist1.round_loss)


def test_scan_carries_server_optimizer_state():
    """Stateful server aggregation (fedadam) rides the fused scan carry:
    both drivers advance the same moments, chunked logging does not
    perturb them, and a second ``run`` resumes from the carried state."""
    agg = AggConfig(name="fedadam", beta1=0.9, beta2=0.99, tau=1e-2,
                    server_lr=0.1)
    fed_scan = _make_fed(agg=agg)
    hist_scan = fed_scan.run(rounds=4, engine="scan")
    fed_loop = _make_fed(agg=agg)
    hist_loop = fed_loop.run(rounds=4, engine="loop")
    _assert_hist_close(hist_scan, hist_loop)
    for a, b in zip(jax.tree.leaves(fed_scan.server_state),
                    jax.tree.leaves(fed_loop.server_state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-5)
    assert int(fed_scan.server_state.step) == 4
    fed_scan.run(rounds=3, log_every=2)  # chunked block + tail round
    assert int(fed_scan.server_state.step) == 7


@pytest.mark.slow
def test_scan_engine_matches_loop_with_pallas_attention():
    """Both round drivers differentiate THROUGH the banded custom-VJP
    attention kernels (DESIGN.md §8) when the runtime override is set:
    scan == loop, and both == the dense-attention run (same math)."""
    fed_loop = _make_fed(use_pallas_attention=True)
    assert fed_loop.gpo_cfg.use_pallas_attention  # override reached cfg
    hist_loop = fed_loop.run(rounds=3, engine="loop")
    fed_scan = _make_fed(use_pallas_attention=True)
    hist_scan = fed_scan.run(rounds=3, engine="scan")
    _assert_hist_close(hist_loop, hist_scan)
    for a, b in zip(jax.tree.leaves(fed_loop.global_params),
                    jax.tree.leaves(fed_scan.global_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-5)
    hist_dense = _make_fed().run(rounds=3, engine="scan")
    _assert_hist_close(hist_dense, hist_scan,
                       tol=dict(rtol=1e-3, atol=1e-4))


def test_interrupted_scan_block_recovers_donated_state():
    """A failure mid fused-scan block after the donated buffers were
    consumed (preemption, OOM, Ctrl-C) must not brick the trainer:
    ``run`` re-raises, rebuilds the opt states / EF residual / fault
    state from the still-valid global params, and the next ``run``
    trains normally."""
    from repro.configs import AvailabilityConfig, CompressionConfig

    data = make_survey_data(SurveyConfig(
        num_groups=8, num_questions=40, d_embed=24, seed=5))
    tr, ev = split_groups(data, seed=5)
    fcfg = FedConfig(
        num_clients=len(tr), rounds=4, local_epochs=1, eval_every=2,
        num_context=6, num_target=6, seed=5,
        compression=CompressionConfig(kind="int8"),
        avail=AvailabilityConfig(online_prob=0.8, crash_prob=0.1,
                                 straggler_prob=0.2, max_staleness=3))
    fed = FederatedGPO(GCFG, fcfg, data, tr, ev)
    hist1 = fed.run(rounds=2, engine="scan")
    assert len(hist1.round_loss) == 2

    real_block = fed._block

    def dying_block(g, opt_s, resid, fault, srv, key, mask):
        # the jit consumed its donated arguments, then the host died
        jax.tree.map(lambda x: x.delete(), opt_s)
        resid.delete()
        jax.tree.map(lambda x: x.delete(), fault)
        raise RuntimeError("simulated preemption mid-block")

    fed._block = dying_block
    with pytest.raises(RuntimeError, match="simulated preemption"):
        fed.run(rounds=2, engine="scan")
    fed._block = real_block

    # every donated buffer was rebuilt (nothing still points at freed
    # device memory), EF restarts at zero, the in-flight buffer is empty
    for leaf in (jax.tree.leaves(fed.opt_states) + [fed.ef_resid]
                 + jax.tree.leaves(fed.fault_state)):
        assert not leaf.is_deleted()
    assert not np.asarray(fed.ef_resid).any()
    assert not np.asarray(fed.fault_state.pending).any()

    hist2 = fed.run(rounds=2, engine="scan")
    assert len(hist2.round_loss) == 2
    assert np.all(np.isfinite(hist2.round_loss))


def test_pallas_aggregation_round_path_matches_stacked():
    hist_jnp = _make_fed().run(rounds=4)
    fed_pal = _make_fed(use_pallas_aggregation=True)
    hist_pal = fed_pal.run(rounds=4)
    _assert_hist_close(hist_jnp, hist_pal, tol=dict(rtol=1e-4, atol=1e-5))


def test_sharded_round_pallas_aggregation_wiring():
    """make_sharded_round with use_pallas_aggregation on a 1-device mesh
    must equal the plain vmap round + fedavg_stacked aggregation."""
    C = 4
    data = make_survey_data(SurveyConfig(
        num_groups=C, num_questions=30, d_embed=16, seed=0))
    gcfg = GPOConfig(d_embed=16, d_model=32, num_layers=1, num_heads=2,
                     d_ff=32)
    fcfg = FedConfig(num_clients=C, local_epochs=2, lr=1e-3,
                     num_context=6, num_target=6,
                     use_pallas_aggregation=True)
    opt = adam(fcfg.lr)
    params = init_gpo_params(gcfg, jax.random.PRNGKey(0))
    groups = jnp.arange(C, dtype=jnp.int32)
    weights = normalize_weights(data.sizes[groups])
    keys = jax.random.split(jax.random.PRNGKey(1), C)
    client_params = broadcast_to_clients(params, C)
    opt_states = jax.vmap(opt.init)(client_params)

    local_train = _make_local_train(gcfg, fcfg, data, opt)
    cp_ref, _, losses_ref = jax.jit(jax.vmap(local_train))(
        client_params, opt_states, keys, groups)
    global_ref = fedavg_stacked(cp_ref, weights)

    mesh = jax.make_mesh((1,), ("data",))
    round_fn = make_sharded_round(gcfg, fcfg, data, mesh, opt=opt)
    agg = make_aggregator(fcfg.agg, num_clients=C,
                          use_pallas=fcfg.use_pallas_aggregation)
    srv = agg.init(params)
    cp_s, _, losses_s, _ = jax.jit(round_fn)(
        client_params, opt_states, keys, groups, weights, srv)

    np.testing.assert_allclose(np.asarray(losses_ref), np.asarray(losses_s),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(global_ref), jax.tree.leaves(cp_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b)[0],
                                   rtol=1e-4, atol=1e-5)
