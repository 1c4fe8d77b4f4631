"""End-to-end driver reproducing the paper's comparison (Figs. 2/4/5):
federated PluralLLM vs centralized GPO on identical data, reporting
convergence round, alignment score, and fairness index.

  PYTHONPATH=src python examples/federated_vs_centralized.py --rounds 300

The paper runs 1300 rounds on an A30 and averages four seeds (§4.1); the
default here is CPU-sized (two seeds x 300 rounds at d_embed 48).
"""
import argparse
from dataclasses import dataclass

import numpy as np

from repro.configs import FedConfig, GPOConfig
from repro.core import CentralizedGPO, FederatedGPO
from repro.core.fairness import convergence_round
from repro.data import SurveyConfig, make_survey_data, split_groups


@dataclass
class RunResult:
    fed_loss: list
    cen_loss: list
    fed_as: list
    cen_as: list
    fed_fi: list
    cen_fi: list


def run_pair(rounds: int, seed: int, num_groups: int = 17,
             num_questions: int = 120, d_embed: int = 48) -> RunResult:
    """One federated and one centralized run on the same data, split and
    seed."""
    data = make_survey_data(SurveyConfig(
        num_groups=num_groups, num_questions=num_questions,
        d_embed=d_embed, seed=seed))
    tr, ev = split_groups(data, train_frac=0.6, seed=seed)
    gcfg = GPOConfig(d_embed=d_embed, d_model=96, num_layers=3,
                     num_heads=4, d_ff=192)
    fcfg = FedConfig(num_clients=len(tr), rounds=rounds, local_epochs=6,
                     lr=3e-4, eval_every=10, num_context=12, num_target=12,
                     seed=seed)
    hist_f = FederatedGPO(gcfg, fcfg, data, tr, ev).run(rounds=rounds)
    hist_c = CentralizedGPO(gcfg, fcfg, data, tr, ev).run(epochs=rounds)
    return RunResult(
        fed_loss=hist_f.round_loss, cen_loss=hist_c.round_loss,
        fed_as=hist_f.eval_mean_as, cen_as=hist_c.eval_mean_as,
        fed_fi=hist_f.eval_fi, cen_fi=hist_c.eval_fi)


def summarize(results: list[RunResult]) -> dict:
    """The paper's three headline numbers, averaged over seeds."""
    speedups, as_improvements, fi_gaps = [], [], []
    fed_conv, cen_conv = [], []
    for r in results:
        rf = convergence_round(np.asarray(r.fed_loss))
        rc = convergence_round(np.asarray(r.cen_loss))
        fed_conv.append(rf)
        cen_conv.append(rc)
        speedups.append(100.0 * (rc - rf) / max(rc, 1))
        as_improvements.append(
            100.0 * (r.fed_as[-1] - r.cen_as[-1]) / max(r.cen_as[-1], 1e-9))
        fi_gaps.append(r.fed_fi[-1] - r.cen_fi[-1])
    return {
        "fed_convergence_round": float(np.mean(fed_conv)),
        "cen_convergence_round": float(np.mean(cen_conv)),
        "convergence_speedup_pct": float(np.mean(speedups)),
        "alignment_improvement_pct": float(np.mean(as_improvements)),
        "fed_final_as": float(np.mean([r.fed_as[-1] for r in results])),
        "cen_final_as": float(np.mean([r.cen_as[-1] for r in results])),
        "fed_final_fi": float(np.mean([r.fed_fi[-1] for r in results])),
        "cen_final_fi": float(np.mean([r.cen_fi[-1] for r in results])),
        "fi_gap": float(np.mean(fi_gaps)),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=300)
    ap.add_argument("--seeds", type=int, nargs="*", default=[0, 1])
    args = ap.parse_args()

    results = [run_pair(args.rounds, s) for s in args.seeds]
    s = summarize(results)

    print("\n=== PluralLLM vs centralized GPO "
          f"({args.rounds} rounds, {len(args.seeds)} seeds) ===")
    print(f"convergence round   : fed {s['fed_convergence_round']:.0f} "
          f"vs cen {s['cen_convergence_round']:.0f} "
          f"-> {s['convergence_speedup_pct']:.1f}% faster (paper: 46%)")
    print(f"eval alignment score: fed {s['fed_final_as']:.4f} "
          f"vs cen {s['cen_final_as']:.4f} "
          f"-> {s['alignment_improvement_pct']:+.2f}% (paper: ~+4%)")
    print(f"fairness index      : fed {s['fed_final_fi']:.4f} "
          f"vs cen {s['cen_final_fi']:.4f} "
          f"-> gap {s['fi_gap']:+.4f} (paper: parity, FI ~= 1)")

    r = results[0]
    print("\nloss curve (fed vs cen, every 25 rounds):")
    for i in range(0, args.rounds, max(25, args.rounds // 10)):
        print(f"  round {i:4d}: fed={r.fed_loss[i]:.4f} "
              f"cen={r.cen_loss[i]:.4f}")


if __name__ == "__main__":
    main()
