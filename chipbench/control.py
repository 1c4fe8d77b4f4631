"""The controls: the reference put in the program's place, computed in the
nearest precision below the one the configuration states, and compared
with the reference by the cell's own numbers. A control has to fail the
cell's limits; its readings set their upper ends.

    python chipbench/control.py --workload <cell> --seeds 1 2 3

For each seed it builds the inputs the cell builds from that seed (survey,
weights, requests) and prints one JSON line per seed with the numbers.
Training: the reference's federation in bfloat16 against the reference
computed as the configuration states (``reference_products``), over the
rounds of the first ``run`` call. Serving: bfloat16 rows (f32
configuration) or int4 weights (int8 configuration) against the
reference, over the same sample of requests a run checks.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


class _Ctx:
    def __init__(self, cell, config, traffic, seed):
        self.cell, self.config, self.traffic, self.seed = (
            cell, config, traffic, seed)


def readings(root: Path, workload: str, seeds, served: int = 2048):
    """Yield (seed, numbers) of the control for each seed. ``served`` is
    how many requests a serving run is taken to have finished (the sample
    is drawn from them as the check draws it)."""
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax.numpy as jnp
    import numpy as np

    from chipbench import common, reference

    _, cell, config, traffic = common.load_cell(root, workload)
    model = config["model"]
    for seed in seeds:
        s = common.seed32(seed)
        if traffic["driver"] == "fed":
            from chipbench.drivers.fed import compare, leaf_norms

            fed, rounds = traffic["fed"], traffic["rounds_per_call"]
            survey = common.make_survey(config["survey"], model["d_embed"])
            w0 = common.make_weights(model, s)
            train_g, _ = common.split_groups(
                config["survey"]["num_groups"], traffic["train_frac"],
                config["survey"]["seed"])
            names = common.WEIGHT_NAMES
            obs = []
            for kw in ({"precision": config["reference_products"]},
                       {"dtype": jnp.bfloat16}):
                losses, g, m = reference.fed_train(w0, survey, train_g, model,
                                                   fed, s, rounds, **kw)
                obs.append({
                    "losses": np.asarray(losses, np.float64),
                    "m_norms": leaf_norms(
                        {k: m[k].astype(jnp.float32) for k in names}, names,
                        batch=True),
                    "d_norms": leaf_norms(
                        {k: g[k].astype(jnp.float32) - w0[k] for k in names},
                        names, batch=False)})
            yield seed, compare(obs[1], obs[0])
        else:
            from chipbench.serve_lib import Serving, gap_stats

            sv = Serving(_Ctx(cell, config, traffic, seed), program=False)
            rids = [sv.make().rid for _ in range(served)]
            pick = sv.sample(rids, traffic["check_requests"], seed)
            ref = sv.reference_rows(pick)
            low = (sv.reference_rows(pick, levels=7.0)
                   if sv.scfg.int8_weights
                   else sv.reference_rows(pick, dtype=jnp.bfloat16))
            stats = gap_stats(low, ref)
            print(f"seed {seed} control row gaps: {stats}", file=sys.stderr,
                  flush=True)
            yield seed, {"row_gap": stats["widest"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed, nums in readings(ROOT, args.workload, args.seeds):
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
