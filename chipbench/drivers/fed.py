"""Driver ``fed``: federated training through ``FederatedGPO.run``.

Set-up builds one ``FederatedGPO`` from the seed's survey and weights and
makes its first ``run`` call: that call compiles (or loads) the fused
round program, and its rounds, each on fresh draws of questions, are the
steps the reference follows. A second call checks that nothing is left to
compile. The window then calls ``run(rounds_per_call, log_every)`` on the
same object until ``--seconds`` have passed; every call's rounds count.

Checked against the reference, its products (and its gradients')
computed as the configuration states, over the first call's rounds:

* ``loss_gap``: the largest relative gap of the first three rounds' mean
  client loss;
* ``adam_m_gap``: each client's Adam first moment after the call (the
  gradient as the optimizer holds it), by the worst leaf: the gap between
  the program's norm and the reference's, over the larger of that leaf's
  reference norm and the median leaf's;
* ``update_gap``: the global weights' change over the call, by the worst
  leaf, measured the same way.

Leaves whose reference gradient (first moment) is under a thousandth of
the median leaf's would move by rounding alone; they are left out.
"""
from __future__ import annotations

import contextlib
import gc
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import common, reference
from chipbench.flops import gpo as gpo_flops
from chipbench.serve_lib import program_params


def leaf_norms(tree: dict, names, batch: bool) -> dict:
    """Per-leaf L2 norms (per client when ``batch``), on the host."""
    if batch:
        return {k: np.asarray(jnp.linalg.norm(
            tree[k].reshape(tree[k].shape[0], -1), axis=1)) for k in names}
    return {k: float(jnp.linalg.norm(tree[k].ravel())) for k in names}


def program_leaves(params) -> dict:
    """The program's parameter pytree as the benchmark's flat names."""
    layers = params["layers"]
    out = {k: getattr(layers, k) for k in common.LAYER_NAMES}
    out.update(in_proj=params["in_proj"], final_norm=params["final_norm"],
               head=params["head"])
    return out


def leaf_gaps(prog: dict, ref: dict, keep):
    """|prog - ref| / max(ref, median leaf of ref) per leaf of ``keep``
    (rows) and client (columns; one column without a client axis), with
    the norms it came from."""
    refs = np.stack([np.atleast_1d(ref[k]) for k in keep])
    progs = np.stack([np.atleast_1d(prog[k]) for k in keep])
    floor = np.median(refs, axis=0, keepdims=True)
    return np.abs(progs - refs) / np.maximum(refs, floor), progs, refs


def norm_gap(prog: dict, ref: dict, keep) -> float:
    """Worst leaf of ``leaf_gaps``."""
    return float(np.max(leaf_gaps(prog, ref, keep)[0]))


def worst_leaf(prog: dict, ref: dict, keep) -> str:
    """Where ``norm_gap`` was read: leaf, client, the two norms and the
    median leaf's reference norm (reported on standard error)."""
    gaps, progs, refs = leaf_gaps(prog, ref, keep)
    i, c = np.unravel_index(np.argmax(gaps), gaps.shape)
    return (f"{keep[i]} client {c}: gap {gaps[i, c]!r}, norm {progs[i, c]!r}"
            f" against {refs[i, c]!r} (median leaf "
            f"{np.median(refs[:, c])!r})")


def kept(ref: dict) -> list:
    """Leaves whose reference first moment is at least a thousandth of
    the median leaf's."""
    grad = {k: float(np.mean(v)) for k, v in ref["m_norms"].items()}
    med = float(np.median(list(grad.values())))
    return [k for k in common.WEIGHT_NAMES if grad[k] >= 1e-3 * med]


def compare(first: dict, ref: dict) -> dict:
    """The three numbers of the module docstring."""
    keep = kept(ref)
    losses_p, losses_r = first["losses"][:3], ref["losses"][:3]
    return {
        "loss_gap": float(np.max(np.abs(losses_p - losses_r)
                                 / np.abs(losses_r))),
        "adam_m_gap": norm_gap(first["m_norms"], ref["m_norms"], keep),
        "update_gap": norm_gap(first["d_norms"], ref["d_norms"], keep),
    }


def setup(ctx):
    """Data, weights and the trainer, from the seed."""
    from repro.configs import AggConfig, FedConfig, GPOConfig
    from repro.core import FederatedGPO
    from repro.core.gpo import GPOLayer
    from repro.data.surveys import SurveyData

    cfg, tr = ctx.config, ctx.traffic
    model, fed = cfg["model"], tr["fed"]
    s = common.seed32(ctx.seed)
    survey = common.make_survey(cfg["survey"], model["d_embed"])
    weights = common.make_weights(model, s)
    train_g, eval_g = common.split_groups(cfg["survey"]["num_groups"],
                                          tr["train_frac"],
                                          cfg["survey"]["seed"])
    fcfg = FedConfig(
        num_clients=fed["num_clients"], num_eval_groups=len(eval_g),
        local_epochs=fed["local_epochs"], lr=fed["lr"],
        eval_every=fed["eval_every"], num_context=fed["num_context"],
        num_target=fed["num_target"], agg=AggConfig(name=fed["agg"]),
        rounds=tr["rounds_per_call"], seed=s)
    data = SurveyData(**{k: survey[k] for k in SurveyData._fields})
    trainer = FederatedGPO(GPOConfig(**model), fcfg, data, train_g, eval_g)
    trainer.global_params = program_params(weights, GPOLayer)
    return trainer, survey, weights, train_g, s


def run(ctx) -> dict:
    tr, model = ctx.traffic, ctx.config["model"]
    fed = tr["fed"]
    rounds, log_every = tr["rounds_per_call"], tr["log_every"]
    names = common.WEIGHT_NAMES
    trainer, survey, weights, train_g, s = setup(ctx)
    sink = open(os.devnull, "w")  # the trainer's round log

    def call():
        with contextlib.redirect_stdout(sink):
            return trainer.run(rounds=rounds, log_every=log_every)

    ctx.mark("survey, weights and trainer built")
    hist = call()
    ctx.mark("first run call (compiles or loads the round program)")
    params = program_leaves(trainer.global_params)
    first = {
        "losses": np.asarray(hist.round_loss),
        "m_norms": leaf_norms(program_leaves(trainer.opt_states.mu), names,
                              batch=True),
        "d_norms": leaf_norms({k: params[k] - weights[k] for k in names},
                              names, batch=False)}
    call()
    ctx.mark("second run call")

    mark = ctx.compiles.count
    calls = 0
    with ctx.window():
        t0 = common.now()
        while True:
            with ctx.spans("cb:fed.run"):
                call()
            calls += 1
            t1 = common.now()
            if t1 - t0 >= ctx.seconds:
                break
    compiles = ctx.compiles.count - mark
    sink.close()
    peak = common.device_peak_bytes()
    del trainer, params
    gc.collect()

    per_call = rounds * fed["num_clients"] * fed["local_epochs"]
    samples = calls * per_call * (fed["num_context"] + fed["num_target"])
    a = ctx.config["survey"]["num_options"]
    step_flops = gpo_flops.train_step(model, fed["num_context"] * a,
                                      fed["num_target"] * a)

    losses, g, m = reference.fed_train(
        weights, survey, train_g, model, fed, s, rounds,
        precision=ctx.config["reference_products"])
    ref = {"losses": np.asarray(losses),
           "m_norms": leaf_norms(m, names, batch=True),
           "d_norms": leaf_norms({k: g[k] - weights[k] for k in names},
                                 names, batch=False)}
    ctx.mark("reference done")
    keep = kept(ref)
    for key in ("m_norms", "d_norms"):
        print(f"worst leaf of {key}: {worst_leaf(first[key], ref[key], keep)}",
              file=sys.stderr, flush=True)
    return {
        "attempted": calls * rounds, "failed": 0,
        "e2e": {"train_samples_per_s": samples / (t1 - t0)},
        "counters": {"window_s": t1 - t0,
                     "model_flops": calls * per_call * step_flops},
        "checks": compare(first, ref),
        "compiles_in_window": compiles,
        "memory_peak_bytes": peak,
    }
