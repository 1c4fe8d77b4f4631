"""Quickstart: train the PluralLLM federated preference predictor on a
synthetic global-opinion survey and query it for an unseen group.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

from repro.configs import FedConfig, GPOConfig
from repro.core import FederatedGPO, predict_preferences
from repro.core.fairness import alignment_score
from repro.data import (
    SurveyConfig,
    make_survey_data,
    sample_icl_batch,
    split_groups,
)


def main() -> None:
    # 1. A synthetic PewResearch-style survey population: 17 groups, 120
    #    multiple-choice questions, frozen-LLM embeddings (stub frontend).
    data = make_survey_data(SurveyConfig(seed=0))
    train_groups, eval_groups = split_groups(data, train_frac=0.6)
    print(f"groups: {len(train_groups)} train clients / "
          f"{len(eval_groups)} held-out")

    # 2. Federated training: each group is a FedAvg client (paper §3).
    #    For differentially-private training (DESIGN.md §9) add
    #      privacy=PrivacyConfig(clip_norm=0.5, noise_multiplier=0.8)
    #    (from repro.configs) — client deltas are then clipped + noised
    #    before aggregation and hist.round_eps tracks the cumulative ε
    #    from the Rényi accountant.
    #    To simulate an unreliable population (DESIGN.md §11) add
    #      avail=AvailabilityConfig(online_prob=0.8, crash_prob=0.05,
    #                               straggler_prob=0.2, max_staleness=4)
    #    — clients then drop out, crash, and upload late on a
    #    deterministic per-seed schedule (hist.round_survivors records
    #    the realized participation); pair it with
    #    agg=AggConfig(name="fedbuff") for staleness-aware buffered
    #    aggregation, and see `dryrun.py --gpo-fed --faults` for the
    #    compiled fault round.
    #    To simulate Byzantine clients (DESIGN.md §13) add
    #      adversary=AdversaryConfig(kind="sign_flip", num_attackers=3)
    #    and pick a defense with agg=AggConfig(name="krum",
    #    num_malicious=3) (or geomedian/median, and/or norm_bound=1.0);
    #    from the CLI the same knobs are `train --trainer gpo
    #    --attack sign_flip --attackers 3 --agg krum`.
    #    For client→edge→server aggregation (DESIGN.md §14) add
    #      hierarchy=HierarchyConfig(num_edges=4)
    #    — each edge pre-reduces its own client block before the
    #    cross-edge hop (the robust family's big all-gather shrinks
    #    from O(C·P) to O(E·P); `dryrun.py --gpo-fed --edges 4` shows
    #    the compiled byte counts).
    gpo_cfg = GPOConfig(d_embed=data.phi.shape[-1])
    fed_cfg = FedConfig(num_clients=len(train_groups), rounds=150,
                        local_epochs=6, lr=3e-4, eval_every=25)
    fed = FederatedGPO(gpo_cfg, fed_cfg, data, train_groups, eval_groups)
    hist = fed.run(rounds=150, log_every=25)

    # 3. Serve: predict an UNSEEN group's answer distribution from a few
    #    in-context examples (the paper's reward-model use case). Under
    #    real query load, use the multi-tenant engine instead
    #    (DESIGN.md §12): core.PreferenceServer adds continuous batching
    #    over ragged requests, a prefix/KV cache for repeated group
    #    contexts, and an int8 weight path — see
    #    examples/serve_preferences.py and `serve --gpo`.
    group = int(eval_groups[0])
    batch = sample_icl_batch(jax.random.PRNGKey(42), data, group,
                             num_context=12, num_target=4)
    pred = predict_preferences(fed.global_params, gpo_cfg, batch.ctx_x,
                               batch.ctx_y, batch.tgt_x, data.num_options)
    truth = batch.tgt_y.reshape(-1, data.num_options)
    print(f"\nunseen group {group}: "
          f"AS={float(alignment_score(pred, truth)):.4f}")
    for i in range(2):
        print(f"  q{i} pred : {np.round(np.asarray(pred[i]), 3).tolist()}")
        print(f"  q{i} truth: {np.round(np.asarray(truth[i]), 3).tolist()}")


if __name__ == "__main__":
    main()
