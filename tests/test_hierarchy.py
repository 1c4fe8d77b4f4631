"""Two-level client→edge→server aggregation (DESIGN.md §14).

Contracts:

1. degeneracy — ``HierarchyConfig()`` (num_edges=1) disables the
   topology *statically*: the pipeline is not restructured,
   ``hier_reduce_flat`` is the flat ``agg.reduce_flat``, and a run with
   an explicit E=1 config is BIT-equal to a default run;
2. linear exactness — for the linear family the edge partial sums
   (against globally-normalized weights) add up to the flat weighted
   mean, so E>1 matches E=1 to reassociation tolerance, both at the
   reduce level and over a full training run;
3. robust semantics — each edge pre-reduces its OWN rows with the
   configured rule (trim depth derived from the C/E edge population),
   then the rule re-runs over the E candidates weighted by edge mass:
   identical rows are a fixed point for every strategy, and the
   two-cluster case lands on the hand-computed server value;
4. engine consistency — scan and loop trace the same hierarchy pipeline
   (bit-equal histories and parameters at E=2);
5. validation — num_edges < 1, non-divisible populations, composition
   with the §11 fault simulator, and a sharded mesh without a matching
   leading edge axis are all rejected eagerly;
6. wire (slow, subprocess) — the compiled sharded schedule's per-op
   collectives show the §14 shrink: robust cross-edge all-gather bytes
   drop from O(C·P) to O(E·P) (4x again with the §10 int8 codec on the
   cross-edge hop), while the linear family's all-reduce total is
   unchanged.
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
    HierarchyConfig,
    PrivacyConfig,
)
from repro.configs.base import AdversaryConfig
from repro.core import FederatedGPO, make_aggregator
from repro.core.federated import make_sharded_round
from repro.core.pipeline import RoundPipeline
from repro.data import SurveyConfig, make_survey_data, split_groups

GCFG = GPOConfig(d_embed=8, d_model=16, num_layers=1, num_heads=2, d_ff=32)

NOCOMP = CompressionConfig(kind="none", error_feedback=False)


def _make_fed(hierarchy=HierarchyConfig(), agg=AggConfig(),
              avail=AvailabilityConfig(), seed=3, rounds=3):
    data = make_survey_data(SurveyConfig(
        num_groups=6, num_questions=24, d_embed=8, seed=seed))
    tr, ev = split_groups(data, seed=seed)  # 4 train groups: E | 4
    fcfg = FedConfig(num_clients=len(tr), rounds=rounds, local_epochs=2,
                     eval_every=2, num_context=4, num_target=4, agg=agg,
                     compression=NOCOMP, avail=avail, hierarchy=hierarchy,
                     seed=seed)
    return FederatedGPO(GCFG, fcfg, data, tr, ev)


def _pipe(agg_cfg=AggConfig(), num_edges=1, num_clients=8):
    return RoundPipeline(
        adversary=AdversaryConfig(), privacy=PrivacyConfig(),
        compression=NOCOMP,
        agg=make_aggregator(agg_cfg, num_clients=num_clients),
        num_clients=num_clients,
        hierarchy=HierarchyConfig(num_edges=num_edges))


# ---------------------------------------------------------------------------
# config + static structure
# ---------------------------------------------------------------------------
def test_hierarchy_config_flags_and_validation():
    assert HierarchyConfig().enabled is False
    assert HierarchyConfig(num_edges=2).enabled is True
    HierarchyConfig(num_edges=2).validate(8)  # divisible: fine
    with pytest.raises(ValueError):
        HierarchyConfig(num_edges=0).validate()
    with pytest.raises(ValueError):
        HierarchyConfig(num_edges=3).validate(8)


def test_e1_is_statically_disabled():
    """num_edges=1 must not restructure the pipeline (the flat fused
    trace keeps riding) and hier_reduce_flat must BE the flat reduce."""
    pipe = _pipe(num_edges=1)
    assert not pipe.restructured
    assert _pipe(num_edges=2).restructured
    vecs = jax.random.normal(jax.random.PRNGKey(0), (8, 7))
    w = jnp.full((8,), 1.0 / 8)
    np.testing.assert_array_equal(
        np.asarray(pipe.hier_reduce_flat(vecs, w)),
        np.asarray(pipe.agg.reduce_flat(vecs, w)))


# ---------------------------------------------------------------------------
# reduce-level semantics
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("num_edges", [2, 4])
def test_linear_edge_partials_sum_to_flat_mean(num_edges):
    """Linear family: edge partial sums against globally-normalized
    weights add up to the exact flat weighted mean (Eq. 2)."""
    key = jax.random.PRNGKey(1)
    vecs = jax.random.normal(key, (8, 11))
    sizes = jnp.arange(1.0, 9.0)
    w = sizes / sizes.sum()
    got = _pipe(num_edges=num_edges).hier_reduce_flat(vecs, w)
    want = np.asarray(w)[:, None] * np.asarray(vecs)
    np.testing.assert_allclose(np.asarray(got), want.sum(0),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize(
    "name", ["median", "trimmed_mean", "krum", "multi_krum", "geomedian"])
def test_identical_rows_are_a_fixed_point(name):
    """Every strategy maps C copies of the same row to that row, through
    both hops — edge candidates equal the row, and so does the server
    rule over the candidates."""
    row = jax.random.normal(jax.random.PRNGKey(2), (9,))
    vecs = jnp.broadcast_to(row, (8, 9))
    w = jnp.full((8,), 1.0 / 8)
    got = _pipe(AggConfig(name=name), num_edges=2).hier_reduce_flat(vecs, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(row),
                               rtol=1e-5, atol=1e-6)


def test_median_two_cluster_server_value():
    """E=2 with each edge internally unanimous: the edge candidates are
    the cluster rows a and b, and the server rule over two equal-mass
    candidates (trim depth k=(2-1)//2=0) is their mean."""
    a = jnp.arange(5.0)
    b = -2.0 * jnp.arange(5.0) + 1.0
    vecs = jnp.concatenate([jnp.broadcast_to(a, (4, 5)),
                            jnp.broadcast_to(b, (4, 5))])
    w = jnp.full((8,), 1.0 / 8)
    got = _pipe(AggConfig(name="median"), num_edges=2).hier_reduce_flat(
        vecs, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray((a + b) / 2.0),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# engine-level degeneracy + equivalence
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("engine", ["scan", "loop"])
def test_e1_run_is_bit_equal_to_default(engine):
    """Explicit num_edges=1 must change NOTHING: same trace, bit-equal
    history and parameters vs. the default config."""
    fed_ref = _make_fed()
    hist_ref = fed_ref.run(rounds=3, engine=engine)
    fed = _make_fed(hierarchy=HierarchyConfig(num_edges=1))
    hist = fed.run(rounds=3, engine=engine)
    assert hist_ref.round_loss == hist.round_loss  # floats, bit-for-bit
    for a, b in zip(jax.tree.leaves(fed_ref.global_params),
                    jax.tree.leaves(fed.global_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_linear_hier_run_matches_flat():
    """FedAvg with E=2 edges reassociates the same weighted sum — a full
    training run stays within float tolerance of the flat run."""
    hist_flat = _make_fed().run(rounds=3, engine="loop")
    fed = _make_fed(hierarchy=HierarchyConfig(num_edges=2))
    hist = fed.run(rounds=3, engine="loop")
    np.testing.assert_allclose(hist.round_loss, hist_flat.round_loss,
                               rtol=1e-4)


def test_scan_loop_bit_equal_with_hierarchy():
    """Both stacked engines trace the same §14 pipeline: E=2 median runs
    agree across scan and loop to float rounding (XLA fuses the two
    programs differently, so the last ulp may differ)."""
    fed_s = _make_fed(hierarchy=HierarchyConfig(num_edges=2),
                      agg=AggConfig(name="median"))
    hist_s = fed_s.run(rounds=3, engine="scan")
    fed_l = _make_fed(hierarchy=HierarchyConfig(num_edges=2),
                      agg=AggConfig(name="median"))
    hist_l = fed_l.run(rounds=3, engine="loop")
    np.testing.assert_allclose(hist_s.round_loss, hist_l.round_loss,
                               rtol=1e-6, atol=0)
    for a, b in zip(jax.tree.leaves(fed_s.global_params),
                    jax.tree.leaves(fed_l.global_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_hier_median_run_trains():
    """End-to-end E=2 median: the hierarchical round still learns."""
    fed = _make_fed(hierarchy=HierarchyConfig(num_edges=2),
                    agg=AggConfig(name="median"), rounds=4)
    hist = fed.run(rounds=4, engine="loop")
    assert len(hist.round_loss) == 4
    assert all(np.isfinite(hist.round_loss))


# ---------------------------------------------------------------------------
# eager rejection
# ---------------------------------------------------------------------------
def test_non_divisible_population_rejected():
    with pytest.raises(ValueError, match="divide"):
        _make_fed(hierarchy=HierarchyConfig(num_edges=3))  # 4 clients


def test_hierarchy_does_not_compose_with_faults():
    faulty = AvailabilityConfig(online_prob=0.7, crash_prob=0.15,
                                straggler_prob=0.3, max_staleness=3)
    with pytest.raises(ValueError, match="fault"):
        _make_fed(hierarchy=HierarchyConfig(num_edges=2), avail=faulty)


def test_sharded_round_requires_edge_axis():
    """hierarchy.num_edges>1 on a mesh without a matching leading edge
    axis must fail at build time, not mis-aggregate silently."""
    data = make_survey_data(SurveyConfig(
        num_groups=5, num_questions=24, d_embed=8, seed=0))
    fcfg = FedConfig(num_clients=4, rounds=1, local_epochs=1,
                     num_context=4, num_target=4, compression=NOCOMP,
                     hierarchy=HierarchyConfig(num_edges=2))
    with pytest.raises(ValueError, match="edge"):
        make_sharded_round(GCFG, fcfg, data,
                           jax.make_mesh((1,), ("data",)))
    with pytest.raises(ValueError, match="edge"):
        make_sharded_round(GCFG, fcfg, data,
                           jax.make_mesh((1, 1), ("edge", "data")),
                           client_axes=("edge", "data"))


def test_client_axes_helper_orders_edge_first():
    from repro.launch.mesh import client_axes
    mesh = jax.make_mesh((1, 1), ("edge", "data"))
    assert client_axes(mesh) == ("edge", "data")
    assert client_axes(jax.make_mesh((1,), ("data",))) == ("data",)


# ---------------------------------------------------------------------------
# compiled two-hop wire (subprocess: forked device count)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_two_hop_collective_bytes():
    """The §14 wire contract, read off the optimized HLO per-op:

    * robust flat: ONE all-gather of C·P floats; edges=4 splits it into
      an intra-edge all-gather of (C/E)·P and a cross-edge all-gather of
      E·P — every hop strictly smaller than the flat gather, and the
      cross-edge hop is E/C of it;
    * robust + int8: the cross-edge hop carries the §10 wire layout —
      4x fewer bytes again (multiplicative with the topology win);
    * linear: the weighted psum over both axes is the SAME total
      all-reduce bytes as the flat psum (a torus all-reduce already IS
      the composed two-hop schedule);
    * edges=1 through the CLI path is byte-identical to flat.
    """
    code = """
import json
from repro.launch.dryrun import lower_gpo_round

def gathers(r):
    # payload gathers only — the per-client weight/mass side-gathers
    # are a few bytes and not part of the O(C*P) claim
    return sorted(b * m for k, b, m in r["collective_ops"]
                  if k == "all-gather" and b * m >= 1024)

out = {}
med_flat = lower_gpo_round("median", clients=8, verbose=False)
med_hier = lower_gpo_round("median", clients=8, edges=4, verbose=False)
med_e1 = lower_gpo_round("median", clients=8, edges=1, verbose=False)
int8_hier = lower_gpo_round("median", clients=8, edges=4,
                            compress="int8", verbose=False)
avg_flat = lower_gpo_round("fedavg", clients=8, verbose=False)
avg_hier = lower_gpo_round("fedavg", clients=8, edges=4, verbose=False)
out["med_flat_ag"] = gathers(med_flat)
out["med_hier_ag"] = gathers(med_hier)
out["med_e1_by_kind"] = med_e1["collective_bytes_by_kind"]
out["med_flat_by_kind"] = med_flat["collective_bytes_by_kind"]
out["int8_hier_ops"] = int8_hier["collective_ops"]
out["avg_flat_ar"] = avg_flat["collective_bytes_by_kind"].get(
    "all-reduce", 0)
out["avg_hier_ar"] = avg_hier["collective_bytes_by_kind"].get(
    "all-reduce", 0)
print(json.dumps(out))
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + sys.path))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])

    # flat robust: one C·P gather; hierarchical: intra (C/E)·P + cross E·P
    [flat_ag] = out["med_flat_ag"]
    hier_ags = out["med_hier_ag"]
    assert len(hier_ags) == 2
    intra, cross = hier_ags
    assert cross == pytest.approx(flat_ag * 4 / 8)  # E/C of the flat hop
    assert intra == pytest.approx(flat_ag * 2 / 8)  # (C/E)/C of it
    assert max(hier_ags) < flat_ag
    # the whole two-hop schedule moves fewer bytes than the flat gather
    assert sum(hier_ags) < 0.8 * flat_ag

    # int8 codec rides the cross-edge hop: an int8 gather at 1/4 the
    # f32 cross-edge payload (plus a tiny f32 scale gather)
    int8_ags = sorted(b * m for k, b, m in out["int8_hier_ops"]
                      if k == "all-gather" and b * m >= 1024)
    assert any(b == pytest.approx(cross / 4) for b in int8_ags)
    assert max(int8_ags) <= intra  # cross-edge no longer dominates

    # linear family: total all-reduce unchanged by the edge mesh
    assert out["avg_hier_ar"] == pytest.approx(out["avg_flat_ar"])
    assert out["avg_flat_ar"] > 0

    # edges=1 through the CLI is the flat schedule, byte-identical
    assert out["med_e1_by_kind"] == out["med_flat_by_kind"]
