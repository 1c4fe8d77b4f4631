"""Driver ``open``: independent scorers arriving on a schedule.

Arrivals are a Poisson process at ``rate`` requests per second: the gaps
are one fixed multiset of exponential draws (from ``shape_seed``) that
the run's seed reorders, scaled so that exactly ``rate * seconds``
requests fall due inside the window. A request is submitted once it is
due, whatever the server is doing; a full queue rejects it, and a
rejected request counts as failed. Latency runs from the due time to the
end of the ``step`` that served it, so a stall also delays the requests
that queue behind it. After the window the server drains what it holds,
for at most a minute. A request that was rejected, or not served by the
end of the drain, was still unanswered when the loop ended: its latency
is taken as the time from its due time to that end, the least it could
be, so it sorts after the served requests that were due with it and the
percentiles stay finite.
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

from chipbench import common
from chipbench.serve_lib import Serving

DRAIN_S = 60.0


def schedule(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    n = int(round(traffic["rate"] * seconds))
    gaps = np.random.default_rng(traffic["shape_seed"]).exponential(
        1.0, n + 1)
    gaps = np.random.default_rng(common.seed32(seed) + 1).permutation(gaps)
    return seconds * np.cumsum(gaps)[:-1] / gaps.sum()


def loop(sv: Serving, ctx, due: np.ndarray, rec: dict):
    """Serve the arrivals ``due`` (seconds from now). ``rec`` gathers
    per-request latency, queue wait, generator lateness, rows and
    batches."""
    srv, spans = sv.server, ctx.spans
    pending = {}
    i, n = 0, len(due)
    t0 = common.now()
    stop = due[-1] + DRAIN_S if n else 0.0
    while True:
        t = common.now() - t0
        with spans("cb:submit"):
            while i < n and due[i] <= t:
                req = sv.make(float(due[i]))
                rec["late"].append(t - due[i])
                if srv.submit(req):
                    pending[req.rid] = due[i]
                else:
                    rec["unserved"][req.rid] = due[i]
                i += 1
        if not srv.queue_depth:
            if i >= n:
                break
            with spans("cb:idle"):
                time.sleep(max(0.0, t0 + due[i] - common.now()))
            continue
        if t > stop:
            break
        ts = common.now() - t0
        with spans("cb:step"):
            out = srv.step()
        te = common.now() - t0
        if not out:
            continue
        with spans("cb:record"):
            b = srv.batches[-1]
            rec["batches"].append(b)
            for rid in b.rids:
                rec["wait"].append(ts - pending[rid])
            for c in out:
                rec["latency"][c.rid] = te - pending.pop(c.rid)
                rec["served"][c.rid] = c.pred
    rec["unserved"].update(pending)  # never served within the drain
    end = common.now() - t0
    for rid, d in rec["unserved"].items():
        rec["latency"][rid] = end - d
    return end


def new_record():
    return {"latency": {}, "unserved": {}, "wait": [], "late": [],
            "served": {}, "batches": []}


def run(ctx) -> dict:
    tr = ctx.traffic
    sv = Serving(ctx)
    ctx.mark("survey, served weights, server and request pool built")
    sv.warm_shapes()
    ctx.mark("every shape warmed")
    loop(sv, ctx, schedule(tr, tr["warmup_seconds"], ctx.seed + 1),
         new_record())
    sv.server.reset(clear_cache=False)

    due = schedule(tr, ctx.seconds, ctx.seed)
    rec = new_record()
    mark = ctx.compiles.count
    with ctx.window():
        elapsed = loop(sv, ctx, due, rec)
    compiles = ctx.compiles.count - mark
    peak = common.device_peak_bytes()
    counters = sv.batch_counters(rec["batches"], sv.server.stats.prefills)
    lat_ms = [v * 1e3 for v in rec["latency"].values()]
    served = rec["served"]
    bad = sum(1 for p in served.values()
              if not (np.all(np.isfinite(p))
                      and np.allclose(p.sum(-1), 1.0, atol=1e-5)))
    late_ms = [v * 1e3 for v in rec["late"]]
    print(f"open loop: {len(due)} due, {len(served)} served, "
          f"{len(rec['unserved'])} rejected or unserved, generator "
          f"lateness p50 {common.percentile(late_ms, 50):.4f} ms, p99 "
          f"{common.percentile(late_ms, 99):.4f} ms, max "
          f"{max(late_ms, default=0.0):.4f} ms", file=sys.stderr, flush=True)
    del sv.server
    gc.collect()
    checks = sv.check(served, tr["check_requests"], ctx.seed)
    return {
        "attempted": len(due), "failed": len(due) - len(served) + bad,
        "e2e": {"serve_p50_ms": common.percentile(lat_ms, 50),
                "serve_p95_ms": common.percentile(lat_ms, 95)},
        "counters": dict(counters, window_s=elapsed,
                         queue_wait_ms=[w * 1e3 for w in rec["wait"]]),
        "checks": checks, "compiles_in_window": compiles,
        "memory_peak_bytes": peak,
    }
