"""The control of the token-text cell: the backbone's plain reference one
precision down (weights and activations in float8_e4m3fn, below the
configuration's bfloat16), compared with the reference by the cell's own
numbers. It has to fail the cell's limits; its readings bound them.

    python chipbench/control_text.py --seeds 1 2 3

For each seed it builds what the cell builds from that seed (backbone,
survey embeddings, served predictor, request pool), draws the check's
sample as a run that finished ``served`` requests would, and prints one
JSON line: ``emb_gap`` of the float8 reference's pooled embeddings
against the float32 reference's, ``row_gap`` of the reference
predictor's rows on the two, and ``row_gap_bf16_predictor`` of the
predictor itself one precision down (bfloat16, below its float32) on the
float32 reference's embeddings.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
CELL = "granite-4-h-small-gpo.bestofn-text"


class _Ctx:
    def __init__(self, config, traffic, seed):
        self.config, self.traffic, self.seed = config, traffic, seed


def readings(root: Path, seeds, served: int = 512):
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax.numpy as jnp
    import numpy as np

    from chipbench import common
    from chipbench.serve_lib import gap_stats
    from chipbench.text_lib import TextServing

    _, _, config, traffic = common.load_cell(root, CELL)
    for seed in seeds:
        sv = TextServing(_Ctx(config, traffic, seed))
        rids = [sv.make().rid for _ in range(served)]
        pick = sv.sample(rids, traffic["check_requests"], seed)
        texts = sv.check_texts(pick)
        ref = sv.reference_pooled(texts)
        low = sv.reference_pooled(texts, control=jnp.float8_e4m3fn)
        rel = [float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
               for a, b in zip(low, ref)]
        exact = sv.reference_rows(pick, ref)
        rows = gap_stats(sv.reference_rows(pick, low), exact)
        rows_bf16 = gap_stats(
            sv.reference_rows(pick, ref, dtype=jnp.bfloat16), exact)
        print(f"seed {seed} control: embedding gaps widest {max(rel)} mean "
              f"{np.mean(rel)}; row gaps {rows}; bfloat16 predictor row "
              f"gaps {rows_bf16}", file=sys.stderr, flush=True)
        yield seed, {"emb_gap": max(rel), "row_gap": rows["widest"],
                     "row_gap_bf16_predictor": rows_bf16["widest"]}
        del sv


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed, nums in readings(ROOT, args.seeds):
        print(json.dumps({"workload": CELL, "seed": seed, "control": nums}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
