"""Pieces every driver shares: seeds, the survey data and weights made from
the seed, the compile counter, host spans, and the peaks table.

Nothing here imports the program. The data and the weights are the
benchmark's own: the program under test and the reference in
``reference.py`` are both handed the same arrays.
"""
from __future__ import annotations

import contextlib
import gc
import json
import math
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

BENCH_DIR = Path(__file__).resolve().parent


def load_cell(root: Path, workload: str):
    """The files a cell names, by name: ``BENCHMARK.json`` and the cell's
    entry in it, its configuration and its traffic mix."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "chipbench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def reader_path(root: Path, metric: str) -> Path:
    """The reader of a per-layer metric: ``layer_metrics/<metric>.py``,
    or else the family's ``layer_metrics/<stem>.py``, the stem being the
    name before its first ``.`` (``idle_share.train`` reads with
    ``idle_share.py``)."""
    d = root / "chipbench" / "layer_metrics"
    own = d / f"{metric}.py"
    return own if own.exists() else d / f"{metric.split('.')[0]}.py"


def seed32(seed: int) -> int:
    """A 31-bit seed from any whole number. ``jax.random.PRNGKey`` keeps
    only the low bits of a large Python int, so two large seeds could
    otherwise collide; SeedSequence mixes all of them."""
    return int(np.random.SeedSequence(int(seed) & (2**128 - 1))
               .generate_state(1)[0] & 0x7FFFFFFF)


# ---------------------------------------------------------------------------
# peaks
# ---------------------------------------------------------------------------
def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# survey data (the arithmetic of a PewResearch-style synthetic population:
# option embeddings, archetype opinion vectors, softmax answer shares)
# ---------------------------------------------------------------------------
def make_survey(survey: dict, d_embed: int) -> dict:
    """Survey arrays on the device in one jitted call: ``phi`` (Q, A, E)
    unit option embeddings, ``prefs`` (G, Q, A) answer shares, ``mask``
    (G, Q) answered questions, ``sizes`` (G,) answered counts.

    The survey is the configuration's dataset, made from its own
    ``survey["seed"]``, not from the run's: the program closes over the
    survey as constants of its compiled round, so a survey per run seed
    would be a new program to compile in every run's set-up."""
    g, q, a = survey["num_groups"], survey["num_questions"], survey["num_options"]
    n_arch = survey["num_archetypes"]
    idio_scale, temp = survey["idiosyncrasy"], survey["temperature"]
    frac_min = survey["min_questions_frac"]
    min_q = max(8, int(frac_min * q) // 2)

    @jax.jit
    def make(key):
        k_phi, k_arch, k_assign, k_idio, k_mask = jax.random.split(key, 5)
        phi = jax.random.normal(k_phi, (q, a, d_embed))
        phi = phi / jnp.linalg.norm(phi, axis=-1, keepdims=True)
        arch = jax.random.normal(k_arch, (n_arch, d_embed))
        assign = jax.random.randint(k_assign, (g,), 0, n_arch)
        w = arch[assign] + idio_scale * jax.random.normal(k_idio, (g, d_embed))
        logits = jnp.einsum("qad,gd->gqa", phi, w,
                            precision="highest") / temp
        prefs = jax.nn.softmax(logits, axis=-1)
        frac = jax.random.uniform(k_mask, (g, q))
        keep = frac_min + (1.0 - frac_min) * jax.random.uniform(
            jax.random.fold_in(k_mask, 1), (g, 1))
        mask = frac < keep
        order = jnp.argsort(~mask, axis=1)
        forced = jnp.zeros_like(mask).at[
            jnp.arange(g)[:, None], order[:, :min_q]].set(True)
        mask = mask | forced
        return {"phi": phi, "prefs": prefs, "mask": mask,
                "sizes": mask.sum(axis=1), "group_w": w}

    return make(jax.random.PRNGKey(survey["seed"]))


def split_groups(num_groups: int, train_frac: float, seed: int):
    """Train / eval group split (the paper's 60/40)."""
    perm = np.random.default_rng(seed).permutation(num_groups)
    n_train = max(1, int(round(train_frac * num_groups)))
    return perm[:n_train], perm[n_train:]


# ---------------------------------------------------------------------------
# weights: LeCun truncated-normal fan-in init, norm scales zero (1 + s)
# ---------------------------------------------------------------------------
WEIGHT_NAMES = ("in_proj", "ln1", "wq", "wk", "wv", "wo", "ln2", "w1", "w2",
                "final_norm", "head")
LAYER_NAMES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w1", "w2")


def make_weights(model: dict, seed: int) -> dict:
    """Predictor weights in float32 on the device, in one jitted call.
    Per-layer leaves are stacked over ``num_layers``."""
    e, d, f, n_l = (model["d_embed"], model["d_model"], model["d_ff"],
                    model["num_layers"])
    out = 2 if model["learn_sigma"] else 1
    shapes = {"in_proj": (e + 2, d), "wq": (n_l, d, d), "wk": (n_l, d, d),
              "wv": (n_l, d, d), "wo": (n_l, d, d), "w1": (n_l, d, f),
              "w2": (n_l, f, d), "head": (d, out)}

    @jax.jit
    def make(key):
        w = {}
        for i, (name, shape) in enumerate(shapes.items()):
            std = 1.0 / math.sqrt(shape[-2])
            w[name] = std * jax.random.truncated_normal(
                jax.random.fold_in(key, i), -2.0, 2.0, shape, jnp.float32)
        w["ln1"] = jnp.zeros((n_l, d), jnp.float32)
        w["ln2"] = jnp.zeros((n_l, d), jnp.float32)
        w["final_norm"] = jnp.zeros((d,), jnp.float32)
        return w

    return make(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# compile counter and host spans
# ---------------------------------------------------------------------------
class CompileCounter:
    """Counts executables built or loaded (in-memory cache misses), from
    JAX's backend-compile event, and how many of them the persistent
    cache held. A window that reads above 0 compiled."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.count = self.hits = self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event, **kw):
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1

    def __str__(self):
        return (f"{self.count} executables in {self.seconds:.2f} s, "
                f"persistent cache {self.hits} hits {self.misses} misses")


class GcPauses:
    """Pauses of the cyclic garbage collector while installed: count,
    total and longest, per generation (reported on standard error)."""

    def __init__(self):
        self.pauses = {0: [], 1: [], 2: []}
        self._t = 0.0

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses[info["generation"]].append(time.perf_counter() - self._t)

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)

    def __str__(self):
        return ", ".join(
            f"gen{g} {len(p)} pauses {sum(p) * 1e3:.1f} ms (longest "
            f"{max(p, default=0.0) * 1e3:.1f} ms)" for g, p in self.pauses.items())


class Spans:
    """Host spans around the calls into each layer. With ``traced`` they
    go into the profiler's trace (``TraceAnnotation``), on the same clock
    as the device's events; otherwise they cost nothing."""

    def __init__(self, traced: bool):
        self.traced = traced

    def __call__(self, name: str):
        if self.traced:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()


def now() -> float:
    return time.perf_counter()


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics; ``inf`` entries (failed requests) sort last."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if xs[hi] == math.inf:
        return math.inf if pos > lo or xs[lo] == math.inf else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def device_peak_bytes() -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def trained_weights(model: dict, survey: dict, spec: dict, seed: int) -> dict:
    """Weights a served predictor would have: ``make_weights`` and then
    ``spec["steps"]`` Adam steps (learning rate ``spec["lr"]``) on the
    seed's survey, each on ``spec["batch"]`` groups with fresh context and
    target draws, in one jitted call. Untrained weights predict shares of
    one sign for every option, which clipping turns into uniform rows; a
    trained predictor's rows are what a server returns."""
    from chipbench import reference

    n_ctx, n_tgt, batch = spec["num_context"], spec["num_target"], spec["batch"]
    n_g = survey["mask"].shape[0]
    n_q, _, e = survey["phi"].shape

    def example_loss(w, data, g, k):
        phi, prefs, mask = data
        p = jax.nn.softmax(jnp.where(mask[g], 0.0, -1e9))
        qs = jax.random.choice(k, n_q, (n_ctx + n_tgt,), replace=False, p=p)
        cq, tq = qs[:n_ctx], qs[n_ctx:]
        cx = phi[cq].reshape(-1, e)
        mu = reference.forward(w, model, cx, prefs[g, cq].reshape(-1),
                               cx.shape[0], phi[tq].reshape(-1, e),
                               precision=None)
        return jnp.mean(jnp.square(mu - prefs[g, tq].reshape(-1)))

    def loss(w, data, k):
        kg, kq = jax.random.split(k)
        groups = jax.random.randint(kg, (batch,), 0, n_g)
        return jnp.mean(jax.vmap(example_loss, (None, None, 0, 0))(
            w, data, groups, jax.random.split(kq, batch)))

    @jax.jit
    def train(w, data, key):
        def step(carry, k):
            w, m, v, t = carry
            g = jax.grad(loss)(w, data, k)
            t = t + 1
            w, m, v = reference._adam(w, g, m, v, t, spec["lr"])
            return (w, m, v, t), None

        zeros = jax.tree.map(jnp.zeros_like, w)
        (w, _, _, _), _ = jax.lax.scan(
            step, (w, zeros, zeros, jnp.zeros((), jnp.int32)),
            jax.random.split(key, spec["steps"]))
        return w

    w0 = make_weights(model, seed)
    data = (survey["phi"], survey["prefs"], survey["mask"])
    return train(w0, data, jax.random.fold_in(jax.random.PRNGKey(seed), 1))
