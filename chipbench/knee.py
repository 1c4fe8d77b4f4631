"""Find the knee of an open-loop serving cell: the highest offered rate
whose queue does not grow over the window.

    python chipbench/knee.py --workload gpo-d4096.online --seed 5 \
        --seconds 10 --rates 400 800 1200 1600

One process, one set-up; each rate runs the cell's open loop for
``--seconds`` and prints the served rate, p50/p95 latency, rejections,
and how much later the last quarter of arrivals was served than the
first (a ratio well above 1 means the queue grew).
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


class _Ctx:
    def __init__(self, config, traffic, seed):
        from chipbench import common

        self.config, self.traffic, self.seed = config, traffic, seed
        self.spans = common.Spans(False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        sys.path.insert(0, p)
    import jax

    from chipbench import common
    from chipbench.drivers import open as open_loop
    from chipbench.serve_lib import Serving

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _, _, config, traffic = common.load_cell(ROOT, args.workload)
    sv = Serving(_Ctx(config, traffic, args.seed))
    sv.warm_shapes()
    for rate in args.rates:
        tr = dict(traffic, rate=rate)
        due = open_loop.schedule(tr, args.seconds, args.seed)
        rec = open_loop.new_record()
        elapsed = open_loop.loop(sv, _Ctx(config, tr, args.seed), due, rec)
        lat = rec["latency"]
        order = sorted(lat)  # rids in arrival order
        q = max(1, len(order) // 4)
        first = statistics.median(lat[r] for r in order[:q])
        last = statistics.median(lat[r] for r in order[-q:])
        ms = [v * 1e3 for v in lat.values()]
        print(json.dumps({
            "rate": rate, "due": len(due), "served": len(rec["served"]),
            "served_per_s": len(rec["served"]) / elapsed,
            "rejected": len(rec["unserved"]),
            "p50_ms": common.percentile(ms, 50),
            "p95_ms": common.percentile(ms, 95),
            "growth": float(last / first),
            "mean_batch": statistics.mean(len(b.rids)
                                          for b in rec["batches"])}),
            flush=True)
        sv.server.reset(clear_cache=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
