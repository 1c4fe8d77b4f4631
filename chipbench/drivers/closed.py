"""Driver ``closed``: a closed loop of scorers against ``PreferenceServer``.

``outstanding`` requests are kept in the server at all times: each time
``step`` retires a batch, as many new requests are submitted. Set-up warms
every shape the traffic can reach, then runs the loop for
``warmup_seconds`` (which fills the prefix cache the way the window
finds it). The window runs the loop until ``--seconds`` have passed;
``serve_rps`` is the requests the window's steps completed over its
length. What is left in the server afterwards is finished outside the
window and checked with the rest.
"""
from __future__ import annotations

import gc

import numpy as np

from chipbench import common
from chipbench.serve_lib import Serving


def loop(sv: Serving, ctx, seconds: float, served: dict, batches: list):
    """The closed loop for ``seconds``; returns (completed, elapsed)."""
    srv, spans = sv.server, ctx.spans
    n_out = ctx.traffic["outstanding"]
    outstanding = srv.queue_depth
    done = 0
    t0 = common.now()
    while True:
        with spans("cb:submit"):
            while outstanding < n_out:
                srv.submit(sv.make())
                outstanding += 1
        with spans("cb:step"):
            out = srv.step()
        with spans("cb:record"):
            for c in out:
                served[c.rid] = c.pred
            batches.append(srv.batches[-1])
            outstanding -= len(out)
            done += len(out)
        t1 = common.now()
        if t1 - t0 >= seconds:
            return done, t1 - t0


def run(ctx) -> dict:
    sv = Serving(ctx)
    ctx.mark("survey, served weights, server and request pool built")
    sv.warm_shapes()
    ctx.mark("every shape warmed")
    loop(sv, ctx, ctx.traffic["warmup_seconds"], {}, [])
    srv = sv.server
    while srv.queue_depth:  # the warm-up's requests leave the server
        srv.step()
    srv.reset(clear_cache=False)
    first_rid = sv.next_rid

    served, batches = {}, []
    mark = ctx.compiles.count
    with ctx.window():
        done, elapsed = loop(sv, ctx, ctx.seconds, served, batches)
    compiles = ctx.compiles.count - mark
    counters = sv.batch_counters(batches, srv.stats.prefills)
    while srv.queue_depth:
        for c in srv.step():
            served[c.rid] = c.pred
    peak = common.device_peak_bytes()
    attempted = sv.next_rid - first_rid
    served = {r: p for r, p in served.items() if r >= first_rid}
    bad = sum(1 for p in served.values()
              if not (np.all(np.isfinite(p))
                      and np.allclose(p.sum(-1), 1.0, atol=1e-5)))
    del srv, sv.server
    gc.collect()
    checks = sv.check(served, ctx.traffic["check_requests"], ctx.seed)
    return {
        "attempted": attempted, "failed": attempted - len(served) + bad,
        "e2e": {"serve_rps": (done - bad) / elapsed},
        "counters": dict(counters, window_s=elapsed),
        "checks": checks, "compiles_in_window": compiles,
        "memory_peak_bytes": peak,
    }
