"""Driver ``closed_text``: the closed loop of ``drivers/closed.py`` with
requests whose targets are token texts, embedded by a frozen backbone
inside ``PreferenceServer.step`` (``text_lib.py``).

Set-up builds the backbone's weights, embeds the survey's texts with
the server's own programs, trains the predictor on them, runs one pass
over the request pool (every batch the window can run, so every program
is compiled here), then the loop for ``warmup_seconds``. The window runs
the loop until ``--seconds`` have passed; ``serve_rps`` is the requests
its steps completed over its length. The check compares the server's
pooled embeddings and served rows with the plain references.
"""
from __future__ import annotations

import numpy as np

from chipbench import common
from chipbench.drivers.closed import loop
from chipbench.flops import granite_h as bb_flops
from chipbench.text_lib import TextServing


def run(ctx) -> dict:
    sv = TextServing(ctx)
    ctx.mark("backbone, survey embeddings, served weights, server and "
             "request pool built")
    sv.warm_shapes()
    ctx.mark("every batch of the pool run once")
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
    rows = int(srv.expert_rows)  # pairs handed to the grouped products
    counters = dict(sv.batch_counters(batches), window_s=elapsed,
                    expert_flops=bb_flops.expert_rows(sv.cfg, rows))
    while srv.queue_depth:
        for c in srv.step():
            served[c.rid] = c.pred
    peak = common.device_peak_bytes()
    attempted = sv.next_rid - first_rid
    served = {r: p for r, p in served.items() if r >= first_rid}
    bad = sum(1 for p in served.values()
              if not (np.all(np.isfinite(p))
                      and np.allclose(p.sum(-1), 1.0, atol=1e-5)))
    checks = sv.check(served, ctx.traffic["check_requests"], ctx.seed)
    return {
        "attempted": attempted, "failed": attempted - len(served) + bad,
        "e2e": {"serve_rps": (done - bad) / elapsed},
        "counters": counters,
        "checks": checks, "compiles_in_window": compiles,
        "memory_peak_bytes": peak,
    }
