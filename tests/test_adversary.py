"""Byzantine attack/defense suite (DESIGN.md §13).

Covers the three contracts the §13 layer makes:

* the adversarial client simulator is deterministic per round (same
  byz key → same attacker set, same corrupted rows) and identical
  across the scan, loop, and sharded engines;
* the benign default is BIT-equal to the pre-§13 round — attack off +
  norm_bound off traces the exact same computation, pinned both at the
  numeric level (scan vs loop, run-to-run) and at the compiled wire
  level (the linear family's collective bytes are unchanged whether
  the attack stage is on or off);
* the defenses (krum / multi_krum / geomedian / norm_bound) actually
  reject outliers, the Pallas (C, C) distance kernel matches its
  oracle, and the composition guard fires on the adaptive+DP+defense
  foot-gun.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (
    AdversaryConfig,
    AggConfig,
    CompressionConfig,
    FedConfig,
    GPOConfig,
    PrivacyConfig,
)
from repro.core import adversary as byz
from repro.core.aggregation import (
    geometric_median_flat,
    krum_scores,
    make_aggregator,
)
from repro.core.federated import (
    FederatedGPO,
    _make_local_train,
    make_sharded_round,
)
from repro.core.fedavg import broadcast_to_clients, normalize_weights
from repro.core.gpo import init_gpo_params
from repro.core.pipeline import STAGE_NAMES, make_pipeline
from repro.data.surveys import SurveyConfig, make_survey_data
from repro.kernels import agg_pairwise_dists
from repro.kernels.ref import ref_pairwise_sq_dists
from repro.optim import adam
from repro.utils.pytree import tree_sub

GCFG = GPOConfig(d_embed=4, d_model=8, num_layers=1, num_heads=1, d_ff=16)


def _data(groups=6, questions=12, d_embed=4):
    return make_survey_data(SurveyConfig(
        num_groups=groups, num_questions=questions, d_embed=d_embed,
        seed=0))


def _run(fcfg, engine, data, rounds=3):
    groups = np.arange(fcfg.num_clients)
    fed = FederatedGPO(GCFG, fcfg, data, groups, groups)
    return fed.run(rounds=rounds, engine=engine)


# ---------------------------------------------------------------------------
# simulator determinism
# ---------------------------------------------------------------------------
def test_byz_key_folds_out_of_round_key():
    k = jax.random.PRNGKey(3)
    bk = byz.fold_byz_key(k)
    assert not np.array_equal(np.asarray(bk), np.asarray(k))
    np.testing.assert_array_equal(np.asarray(bk),
                                  np.asarray(byz.fold_byz_key(k)))


def test_attacker_mask_exact_count_and_determinism():
    bk = byz.fold_byz_key(jax.random.PRNGKey(0))
    for c, f in [(8, 3), (5, 0), (4, 9)]:
        m = byz.attacker_mask(bk, c, f)
        assert m.shape == (c,) and m.dtype == jnp.bool_.dtype
        assert int(m.sum()) == min(f, c)
        np.testing.assert_array_equal(np.asarray(m),
                                      np.asarray(byz.attacker_mask(bk, c, f)))
    # a different round key re-draws the population
    bk2 = byz.fold_byz_key(jax.random.PRNGKey(1))
    masks = [np.asarray(byz.attacker_mask(k, 64, 16)) for k in (bk, bk2)]
    assert not np.array_equal(*masks)


def test_attack_rows_bit_identical_under_subsampling():
    """Client g's corrupted row depends only on (byz_key, g): computing
    the attack over the full population or over any gid subset yields
    byte-identical rows for the shared clients — the scan/loop/sharded
    replay contract."""
    c, p = 8, 17
    bk = byz.fold_byz_key(jax.random.PRNGKey(5))
    vecs = jax.random.normal(jax.random.PRNGKey(6), (c, p))
    adv = AdversaryConfig(kind="gaussian", num_attackers=3)
    mask = byz.attacker_mask(bk, c, adv.num_attackers)
    full = byz.apply_attack(vecs, mask, adv, bk, jnp.arange(c))
    sub = jnp.asarray([1, 4, 6])
    part = byz.apply_attack(vecs[sub], mask[sub], adv, bk, sub)
    np.testing.assert_array_equal(np.asarray(full)[np.asarray(sub)],
                                  np.asarray(part))


def test_attack_semantics_on_flat_rows():
    c, p = 6, 5
    bk = byz.fold_byz_key(jax.random.PRNGKey(2))
    vecs = jax.random.normal(jax.random.PRNGKey(3), (c, p))
    mask = jnp.asarray([True, False, True, False, False, False])
    gids = jnp.arange(c)

    out = byz.apply_attack(vecs, mask, AdversaryConfig(
        kind="sign_flip", num_attackers=2), bk, gids)
    np.testing.assert_allclose(np.asarray(out[0]), -np.asarray(vecs[0]))
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(vecs[1]))

    out = byz.apply_attack(vecs, mask, AdversaryConfig(
        kind="scaled", num_attackers=2, scale=7.0), bk, gids)
    np.testing.assert_allclose(np.asarray(out[2]),
                               7.0 * np.asarray(vecs[2]), rtol=1e-6)

    # ALIE rows collapse onto mean + z*std of the HONEST rows only
    adv = AdversaryConfig(kind="alie", num_attackers=2)
    out = byz.apply_attack(vecs, mask, adv, bk, gids)
    mean, std = byz.honest_stats(vecs.astype(jnp.float32), mask)
    np.testing.assert_allclose(
        np.asarray(out[0]),
        np.asarray(mean + adv.alie_z * std), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[2]))

    # disabled / data-level attacks are the identity on the wire
    for adv in (AdversaryConfig(),
                AdversaryConfig(kind="label_flip", num_attackers=2)):
        np.testing.assert_array_equal(
            np.asarray(byz.apply_attack(vecs, mask, adv, bk, gids)),
            np.asarray(vecs))


def test_flip_preferences_stays_on_simplex_and_reverses_order():
    a = 4
    key = jax.random.PRNGKey(9)
    logits = jax.random.normal(key, (5, a))
    y = jax.nn.softmax(logits, axis=-1)  # rows on the simplex
    flipped = byz.flip_preferences(y.reshape(-1), a).reshape(5, a)
    np.testing.assert_allclose(np.asarray(flipped.sum(-1)),
                               np.ones(5), rtol=1e-5)
    assert np.all(np.asarray(flipped) >= 0)
    # exactly reversed preference ordering per question
    np.testing.assert_array_equal(
        np.argsort(np.asarray(y), axis=-1),
        np.argsort(np.asarray(flipped), axis=-1)[:, ::-1])
    for q in range(5):
        assert (np.argmax(np.asarray(y)[q])
                == np.argmin(np.asarray(flipped)[q]))
        assert (np.argmin(np.asarray(y)[q])
                == np.argmax(np.asarray(flipped)[q]))


# ---------------------------------------------------------------------------
# defenses
# ---------------------------------------------------------------------------
def test_krum_selects_honest_row_against_outliers():
    c, p, f = 9, 11, 3
    honest = jax.random.normal(jax.random.PRNGKey(0), (c - f, p))
    bad = 50.0 * jnp.ones((f, p))
    vecs = jnp.concatenate([honest, bad], axis=0)
    w = jnp.full((c,), 1.0 / c)
    scores = krum_scores(vecs, w, f)
    assert int(jnp.argmin(scores)) < c - f  # never an outlier
    # the fused-kernel scores agree with the jnp path
    np.testing.assert_allclose(
        np.asarray(scores),
        np.asarray(krum_scores(vecs, w, f, use_pallas=True)),
        rtol=1e-4, atol=1e-4)


def test_geomedian_rejects_outliers_mean_does_not():
    c, p, f = 10, 7, 3
    honest = jax.random.normal(jax.random.PRNGKey(1), (c - f, p))
    vecs = jnp.concatenate([honest, 50.0 * jnp.ones((f, p))], axis=0)
    w = jnp.full((c,), 1.0 / c)
    gm = geometric_median_flat(vecs, w, iters=50, eps=1e-6)
    m_honest = jnp.mean(honest, axis=0)
    m_all = jnp.average(vecs, axis=0, weights=w)
    d_gm = float(jnp.linalg.norm(gm - m_honest))
    d_mean = float(jnp.linalg.norm(m_all - m_honest))
    assert d_gm < 0.2 * d_mean  # the mean is dragged ~f/c * 50, gm is not


def test_norm_clip_rows_bounds_and_preserves_small():
    vecs = jnp.asarray([[3.0, 4.0], [0.3, 0.4], [0.0, 0.0]])
    out = np.asarray(byz.norm_clip_rows(vecs, 1.0))
    np.testing.assert_allclose(np.linalg.norm(out[0]), 1.0, rtol=1e-6)
    np.testing.assert_allclose(out[1], [0.3, 0.4], rtol=1e-6)
    np.testing.assert_array_equal(out[2], [0.0, 0.0])


def test_pairwise_kernel_matches_oracle():
    x = jax.random.normal(jax.random.PRNGKey(4), (6, 33))
    ref = np.asarray(ref_pairwise_sq_dists(x))
    out = np.asarray(agg_pairwise_dists(x, interpret=True))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_defense_composition_guard():
    base = dict(num_clients=4, rounds=1,
                adversary=AdversaryConfig(kind="sign_flip",
                                          num_attackers=1),
                privacy=PrivacyConfig(clip_norm=1.0, noise_multiplier=0.5))
    ok = FedConfig(agg=AggConfig(name="krum", num_malicious=1), **base)
    byz.check_defense_composition(ok)  # loss-free defense: silent

    bad = FedConfig(agg=AggConfig(name="adaptive"), strict_privacy=False,
                    **base)
    with pytest.warns(UserWarning, match="attacker-steerable"):
        byz.check_defense_composition(bad)

    strict = FedConfig(agg=AggConfig(name="adaptive"), strict_privacy=True,
                       **base)
    with pytest.raises(ValueError, match="attacker-steerable"):
        byz.check_defense_composition(strict)


# ---------------------------------------------------------------------------
# stage pipeline: every engine assembles the same declared stage list
# ---------------------------------------------------------------------------
def test_stage_list_shared_across_engines():
    fcfg = FedConfig(num_clients=6,
                     adversary=AdversaryConfig(kind="scaled",
                                               num_attackers=2),
                     privacy=PrivacyConfig(clip_norm=1.0),
                     compression=CompressionConfig(kind="int8"),
                     agg=AggConfig(name="krum", num_malicious=2))
    agg = make_aggregator(fcfg.agg, num_clients=6)
    pipe = make_pipeline(fcfg, agg=agg, num_clients=6)
    assert tuple(n for n, _ in pipe.stages()) == STAGE_NAMES
    assert all(on for _, on in pipe.stages())
    assert pipe.restructured

    off = FedConfig(num_clients=6)
    pipe_off = make_pipeline(off, agg=make_aggregator(off.agg,
                                                      num_clients=6),
                             num_clients=6)
    assert [n for n, on in pipe_off.stages() if on] == ["local_train",
                                                        "aggregate"]
    assert not pipe_off.restructured  # benign default: pre-§13 trace


# ---------------------------------------------------------------------------
# engine equivalence (scan == loop == sharded) under attack
# ---------------------------------------------------------------------------
def test_attack_off_round_is_deterministic_and_engine_invariant():
    """The benign default pins the pre-§13 numerics: scan and loop agree
    to float rounding (XLA fuses the two programs differently, so the
    last ulp may differ), and reruns reproduce exactly."""
    data = _data()
    fcfg = FedConfig(num_clients=6, rounds=3, local_epochs=2,
                     num_context=3, num_target=3, eval_every=10)
    h_scan = _run(fcfg, "scan", data)
    h_loop = _run(fcfg, "loop", data)
    np.testing.assert_allclose(h_scan.round_loss, h_loop.round_loss,
                               rtol=1e-6, atol=0)
    np.testing.assert_array_equal(h_scan.round_loss,
                                  _run(fcfg, "scan", data).round_loss)


@pytest.mark.parametrize("kind,aggname", [
    ("sign_flip", "krum"),
    ("alie", "geomedian"),
    ("label_flip", "multi_krum"),
])
def test_attacked_round_scan_matches_loop(kind, aggname):
    data = _data()
    fcfg = FedConfig(num_clients=6, rounds=3, local_epochs=2,
                     num_context=3, num_target=3, eval_every=10,
                     adversary=AdversaryConfig(kind=kind,
                                               num_attackers=2),
                     agg=AggConfig(name=aggname, num_malicious=2,
                                   multi_krum_m=3))
    h_scan = _run(fcfg, "scan", data)
    h_loop = _run(fcfg, "loop", data)
    np.testing.assert_allclose(h_scan.round_loss, h_loop.round_loss,
                               rtol=1e-6, atol=0)
    # the attack visibly perturbed the trajectory
    clean = FedConfig(num_clients=6, rounds=3, local_epochs=2,
                      num_context=3, num_target=3, eval_every=10)
    assert not np.array_equal(h_scan.round_loss,
                              _run(clean, "scan", data).round_loss)


@pytest.mark.parametrize("adv,aggcfg", [
    (AdversaryConfig(kind="sign_flip", num_attackers=2),
     AggConfig(name="krum", num_malicious=2)),
    (AdversaryConfig(kind="alie", num_attackers=2),
     AggConfig(name="geomedian", norm_bound=2.0)),
    (AdversaryConfig(kind="label_flip", num_attackers=2),
     AggConfig(name="multi_krum", num_malicious=2, multi_krum_m=3)),
])
def test_sharded_attacked_round_matches_stacked(adv, aggcfg):
    """One full attacked round through ``make_sharded_round`` on a
    1-device mesh lands on the stacked pipeline's update (the ALIE
    psum'd honest stats, the replicated byz key, and the all-gathered
    robust reduce all agree with their stacked counterparts)."""
    c = 5
    gcfg = GPOConfig(d_embed=8, d_model=8, num_layers=1, num_heads=1,
                     d_ff=16)
    data = _data(groups=c, questions=24, d_embed=8)
    fcfg = FedConfig(num_clients=c, local_epochs=2, lr=1e-3,
                     num_context=4, num_target=4, adversary=adv,
                     agg=aggcfg)
    opt = adam(fcfg.lr)
    agg = make_aggregator(fcfg.agg, num_clients=c)
    params = init_gpo_params(gcfg, jax.random.PRNGKey(0))
    server_state = agg.init(params)
    groups = jnp.arange(c, dtype=jnp.int32)
    weights = normalize_weights(data.sizes[groups])
    k_round = jax.random.PRNGKey(7)
    keys = jax.random.split(k_round, c)
    bk = byz.fold_byz_key(k_round)
    client_params = broadcast_to_clients(params, c)
    opt_states = jax.vmap(opt.init)(client_params)

    pipe = make_pipeline(fcfg, agg=agg, num_clients=c)
    local_train = _make_local_train(gcfg, fcfg, data, opt)
    if pipe.flip_data:
        att = pipe.attacked_flags(bk, groups)
        cp_ref, _, losses = jax.jit(jax.vmap(local_train))(
            client_params, opt_states, keys, groups, att)
    else:
        cp_ref, _, losses = jax.jit(jax.vmap(local_train))(
            client_params, opt_states, keys, groups)
    deltas = tree_sub(cp_ref, client_params)
    global_ref, _, _ = pipe.reduce_apply(
        server_state, params, deltas, weights, keys, losses=losses,
        idx=groups, resid=None, byz_key=bk)

    mesh = jax.make_mesh((1,), ("data",))
    round_fn = make_sharded_round(gcfg, fcfg, data, mesh, opt=opt,
                                  agg=agg)
    cp_s, _, _, _ = jax.jit(round_fn)(
        client_params, opt_states, keys, groups, weights, server_state,
        bk)
    for a, b in zip(jax.tree.leaves(global_ref), jax.tree.leaves(cp_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b)[0],
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# compiled wire: the linear family's collectives are attack-invariant
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_attack_stage_keeps_linear_collective_bytes():
    """hlo_cost acceptance pin (DESIGN.md §13): turning the attack stage
    on must not change the compiled collective schedule of the linear
    family — same single parameter-sized all-reduce, byte-identical.
    Subprocess because the 8-device host-platform override is
    process-global."""
    code = """
import json
from repro.launch.dryrun import lower_gpo_round
out = {}
for attack in ("none", "sign_flip"):
    r = lower_gpo_round("fedavg", clients=8, attack=attack, attackers=2,
                        verbose=False)
    out[attack] = r["collective_bytes_by_kind"]
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
    assert out["sign_flip"] == out["none"]
    assert out["none"].get("all-reduce", 0) > 0
