"""Set-up, request generation, shape warm-up, counters and the correctness
check that the serving drivers (``drivers/closed.py``, ``drivers/open.py``)
share. The system under test is ``PreferenceServer`` and its ``step``."""
from __future__ import annotations

import sys

import numpy as np

from chipbench import common, reference
from chipbench.flops import gpo as gpo_flops
from chipbench.flops import int8_matmul as int8_flops


def program_params(weights: dict, GPOLayer) -> dict:
    """The benchmark's weights in the program's pytree."""
    return {"in_proj": weights["in_proj"],
            "layers": GPOLayer(**{k: weights[k] for k in common.LAYER_NAMES}),
            "final_norm": weights["final_norm"], "head": weights["head"]}


def _bucket(n, buckets):
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"length {n} exceeds the largest bucket {buckets[-1]}")


class Request:
    """One scoring request as the benchmark keeps it: host arrays and the
    prefix key the caller would send."""

    __slots__ = ("ctx_x", "ctx_y", "tgt_x", "key")

    def __init__(self, ctx_x, ctx_y, tgt_x, key):
        self.ctx_x, self.ctx_y, self.tgt_x, self.key = ctx_x, ctx_y, tgt_x, key


class Serving:
    """Everything a serving cell builds before its window."""

    def __init__(self, ctx, *, program: bool = True):
        from repro.configs import GPOConfig, ServeConfig
        from repro.core.gpo import GPOLayer
        from repro.core.serving import PreferenceServer, Request as PRequest

        self.PRequest = PRequest
        cfg, tr = ctx.config, ctx.traffic
        self.model = cfg["model"]
        self.survey_cfg = cfg["survey"]
        self.products = cfg["reference_products"]
        self.a = self.survey_cfg["num_options"]
        s = common.seed32(ctx.seed)
        survey = common.make_survey(self.survey_cfg, self.model["d_embed"])
        self.weights = common.trained_weights(
            self.model, survey, cfg["served_weights"], s)
        self.phi = np.asarray(survey["phi"])
        self.prefs = np.asarray(survey["prefs"])
        self.mask = np.asarray(survey["mask"])
        serve = {k: tuple(v) if isinstance(v, list) else v
                 for k, v in cfg["serve"].items()}
        self.scfg = ServeConfig(**serve)
        self.server = PreferenceServer(
            program_params(self.weights, GPOLayer), GPOConfig(**self.model),
            self.scfg, num_options=self.a) if program else None
        self.ctx_range = tuple(tr["ctx_questions"])
        self.tgt_range = tuple(tr["tgt_questions"])
        self.pool = self._pool(tr, ctx.seed)
        self.lengths = {}  # rid -> (ctx points, tgt points, prefix key)
        self.requests = {}  # rid -> Request, for the check
        self.next_rid = 0

    # -- request generation ---------------------------------------------
    def _pool(self, tr, seed):
        """``tr["pool"]`` requests. Their sizes are one fixed multiset (from
        ``tr["shape_seed"]``); which questions and answers they carry
        comes from the seed. With fresh contexts the seed also reorders
        the sizes and draws each request's group. With a context per
        group the server's batches are fixed runs of the pool, which it
        cycles through, so the sizes, their order and the groups all come
        from ``shape_seed``: every seed then runs the same batches."""
        n = tr["pool"]
        g_all = self.mask.shape[0]
        shape_rng = np.random.default_rng(tr["shape_seed"])
        t_sizes = shape_rng.integers(self.tgt_range[0], self.tgt_range[1] + 1,
                                     n)
        per_group = tr["contexts"] == "per_group"
        m_sizes = shape_rng.integers(self.ctx_range[0], self.ctx_range[1] + 1,
                                     g_all if per_group else n)
        rng = np.random.default_rng(common.seed32(seed))
        if per_group:
            groups = shape_rng.integers(g_all, size=n)
        else:
            t_sizes = rng.permutation(t_sizes)
            m_sizes = rng.permutation(m_sizes)
        d = self.phi.shape[-1]

        def context(g, m):
            answered = np.flatnonzero(self.mask[g])
            q = rng.choice(answered, size=m, replace=False)
            return (q, self.phi[q].reshape(-1, d),
                    self.prefs[g, q].reshape(-1).astype(np.float32))

        group_ctx = ([context(g, int(m_sizes[g])) for g in range(g_all)]
                     if per_group else None)
        pool = []
        for i in range(n):
            g = int(groups[i]) if per_group else int(rng.integers(g_all))
            if per_group:
                cq, cx, cy = group_ctx[g]
                key = ("group", g)
            else:
                cq, cx, cy = context(g, int(m_sizes[i]))
                key = None  # a fresh context: the caller's key is unique
            rest = np.setdiff1d(np.flatnonzero(self.mask[g]), cq)
            tq = rng.choice(rest, size=int(t_sizes[i]), replace=False)
            pool.append(Request(cx, cy, self.phi[tq].reshape(-1, d), key))
        return pool

    def make(self, due: float = 0.0):
        """The next request of the pool as the program's ``Request``."""
        rid = self.next_rid
        self.next_rid += 1
        r = self.pool[rid % len(self.pool)]
        key = r.key if r.key is not None else ("fresh", rid)
        self.lengths[rid] = (r.ctx_x.shape[0], r.tgt_x.shape[0], key)
        self.requests[rid] = r
        return self.PRequest(rid=rid, ctx_x=r.ctx_x, ctx_y=r.ctx_y,
                             tgt_x=r.tgt_x, prefix_key=key, arrival=due)

    # -- warm-up of every shape the traffic can reach --------------------
    def warm_shapes(self):
        """Drive ``step`` through every (batch size, context bucket, target
        bucket) the traffic can reach, with cache misses at one and at two
        context buckets and the same requests again as hits, so that every
        program and every eager operation of the window is compiled (or
        loaded from the persistent cache) here."""
        a, sc, srv = self.a, self.scfg, self.server
        d = self.phi.shape[-1]
        ctx_lens = sorted({m * a for m in range(self.ctx_range[0],
                                               self.ctx_range[1] + 1)})
        tgt_lens = sorted({t * a for t in range(self.tgt_range[0],
                                               self.tgt_range[1] + 1)})
        by_cb = {}
        for n in ctx_lens:
            by_cb.setdefault(_bucket(n, sc.ctx_buckets), n)
        by_tb = {}
        for n in tgt_lens:
            by_tb.setdefault(_bucket(n, sc.tgt_buckets), n)
        small = by_cb[min(by_cb)]
        zeros = np.zeros((max(ctx_lens + tgt_lens), d), np.float32)
        tag = 0
        for tb, tl in by_tb.items():
            for cb, cl in by_cb.items():
                for n in range(1, sc.max_batch + 1):
                    for lens in ([cl] * n, [cl] + [small] * (n - 1)):
                        keys = []
                        for m in lens:
                            tag += 1
                            keys.append((("warm", tag), m))
                        for _ in range(2):  # misses, then the same as hits
                            for key, m in keys:
                                srv.submit(self.PRequest(
                                    rid=-1, ctx_x=zeros[:m],
                                    ctx_y=zeros[:m, 0],
                                    tgt_x=zeros[:tl], prefix_key=key))
                            srv.step()
        srv.reset(clear_cache=True)

    # -- counters ----------------------------------------------------------
    def batch_counters(self, batches, prefills: int) -> dict:
        """Points computed and useful, model FLOPs (useful rows), and the
        int8 kernel's FLOPs, over ``batches`` (``BatchRecord`` list).

        ``BatchRecord`` holds the decode batch but not how the misses were
        prefilled, so the prefill groups are derived here by the server's
        rule (one prefill per distinct missed key, grouped by context
        bucket). ``prefills`` is the server's own count of contexts it
        prefilled over the same steps (``ServeStats.prefills``); where the
        derived count differs, the rule has drifted from the server's and
        the padding counts are left out (``None``)."""
        sc, model, a = self.scfg, self.model, self.a
        computed = useful = 0
        derived = 0
        flops = k_flops = 0.0
        for b in batches:
            seen = set()
            groups = {}
            for rid, hit in zip(b.rids, b.hits):
                m, t, key = self.lengths[rid]
                useful += t
                flops += gpo_flops.decode(model, m, t)
                if hit or key in seen:
                    continue
                seen.add(key)
                groups.setdefault(_bucket(m, sc.ctx_buckets), []).append(m)
            computed += b.batch_pad * b.tgt_bucket
            if self.scfg.int8_weights:
                k_flops += int8_flops.gpo_pass(model, b.batch_pad,
                                               b.tgt_bucket, head=True)
            for cb, ms in groups.items():
                derived += len(ms)
                gb = _bucket(len(ms), sc.batch_buckets)
                computed += gb * cb
                useful += sum(ms)
                flops += sum(gpo_flops.prefill(model, m) for m in ms)
                if self.scfg.int8_weights:
                    k_flops += int8_flops.gpo_pass(model, gb, cb, head=False)
        if derived != prefills:
            print(f"prefill groups: {derived} derived, {prefills} counted "
                  f"by the server; padding left out", file=sys.stderr)
            computed = useful = None
        return {"points_computed": computed, "points_useful": useful,
                "model_flops": flops, "int8_flops": k_flops}

    # -- correctness -----------------------------------------------------
    def sample(self, rids, n_sample: int, seed: int) -> list:
        """``n_sample`` of ``rids`` drawn from the seed, with the request of
        most target rows (then most context) first."""
        rids = sorted(rids)
        longest = max(rids, key=lambda r: (self.lengths[r][1],
                                           self.lengths[r][0]))
        rest = [r for r in rids if r != longest]
        rng = np.random.default_rng(common.seed32(seed) + 17)
        pick = rng.choice(rest, size=min(n_sample - 1, len(rest)),
                          replace=False)
        return [longest] + [int(r) for r in pick]

    def reference_rows(self, rids, **kw) -> list:
        """Reference rows of ``rids``; ``kw`` selects a control precision
        (``dtype``) or integer levels (``levels``). The weights are served
        as int8 where the configuration says so, and the products are
        computed as it states (``reference_products``) unless a control
        precision is asked for."""
        if "levels" not in kw and self.scfg.int8_weights:
            kw["levels"] = 127.0
        if "dtype" not in kw:
            kw["precision"] = self.products
        reqs = [(self.requests[r].ctx_x, self.requests[r].ctx_y,
                 self.requests[r].tgt_x) for r in rids]
        return reference.serve_rows(
            self.weights, self.model, self.a, reqs,
            m_pad=self.ctx_range[1] * self.a,
            t_pad=self.tgt_range[1] * self.a, **kw)

    def check(self, served: dict, n_sample: int, seed: int) -> dict:
        """Widest gap between the rows the window served and the
        reference's, over a sample of the finished requests."""
        pick = self.sample(served, n_sample, seed)
        ref = self.reference_rows(pick)
        stats = gap_stats([served[r] for r in pick], ref)
        print(f"row gaps: {stats}", file=sys.stderr, flush=True)
        return {"row_gap": stats["widest"]}


def gap_stats(rows, ref) -> dict:
    """The widest gap between two lists of row arrays, and two steadier
    readings beside it: the mean over requests of each one's widest gap,
    and the mean gap of every answer."""
    widest = [float(np.max(np.abs(a - b))) for a, b in zip(rows, ref)]
    every = np.concatenate([np.abs(a - b).ravel() for a, b in zip(rows, ref)])
    return {"widest": max(widest), "mean_widest": float(np.mean(widest)),
            "mean": float(np.mean(every))}
