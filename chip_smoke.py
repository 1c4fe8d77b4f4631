"""Chip smoke test: drive both served paths once on a TPU and check them.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # four chips of one host

One chip runs, in this order:

1. device check: JAX's first device must be a TPU;
2. federated training: ``FederatedGPO`` with the paper's ``FedConfig``
   (10 clients, 6 local epochs, Adam 3e-4, 16 + 16 questions) at the
   frozen-backbone width ``d_embed=4096``, a few blocks of rounds; the
   loss must be finite and fall;
3. Pallas on the chip: one round with the Pallas attention and
   aggregation kernels against the dense round from the same keys; the
   round's program must hold the kernels as ``tpu_custom_call``;
4. serving: ``PreferenceServer`` on the trained predictor, f32 and int8
   weights, against a direct ``predict_preferences`` per request.

``--four-chips`` runs only the sharded round (``make_sharded_round``, 8
clients, 2 per chip) with fedavg (one psum) and median (an all-gather),
each against the stacked one-device round from the same keys.

Any failed check exits non-zero. The last line of standard output is
``{"ok": true, "device": {...}}`` and is printed only when every check
passed. Times printed are wall-clock information, not metrics.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (  # noqa: E402
    AggConfig, FedConfig, GPOConfig, ServeConfig)
from repro.core import (  # noqa: E402
    FederatedGPO, PreferenceServer, broadcast_to_clients, init_gpo_params,
    make_aggregator, make_request_trace, make_sharded_round,
    normalize_weights, predict_preferences)
from repro.core.serving import _prefill_batch  # noqa: E402
from repro.data import SurveyConfig, make_survey_data, split_groups  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.sharding import server_state_shardings  # noqa: E402
from repro.optim import adam  # noqa: E402
from repro.utils.runtime import device_info, enable_compile_cache  # noqa: E402

# Paper-scale widths (configs/base.py): GPOConfig defaults with the
# frozen-backbone embedding of a 7B model.
D_EMBED = 4096
TRAIN_ROUNDS = 20
LOG_EVERY = 5  # rounds per fused scan block
REQUESTS = 32
HIT_RATIO = 0.5
FOUR_CHIP_CLIENTS = 8

# Round against round, same keys (Pallas vs dense, sharded vs stacked).
# The round loss is compared as run; the parameters with f32 matmuls
# (``default_matmul_precision("highest")``) on both sides. With the TPU's
# default one-pass bf16 dots two programs round differently, and Adam's
# first steps turn that into a flipped step (up to lr = 3e-4 each)
# wherever a gradient is near zero. The parameter bound is half a step.
ROUND_LOSS_RTOL = 1e-3
ROUND_F32_LOSS_RTOL = 1e-4
ROUND_F32_PARAM_ATOL = 1.5e-4
# Served rows (on the simplex) against a direct predict_preferences:
# f32 weights with f32 matmuls on both paths; int8 weights as served.
SERVE_F32_ATOL = 1e-5
SERVE_INT8_ATOL = 0.05  # max-abs bound of the int8 weight path (PR 7)

_failures: list[str] = []


def check(ok: bool, what: str) -> None:
    print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        _failures.append(what)


def max_abs_diff(a, b) -> float:
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def kernel_names(jitted, *args) -> set:
    """Pallas kernels that ``jitted`` lowers to as ``tpu_custom_call``
    (none when the kernels ran in interpret mode)."""
    text = jitted.lower(*args).as_text()
    return {m for line in text.splitlines() if "tpu_custom_call" in line
            for m in re.findall(r'kernel_name = "(\w+)"', line)}


def precision(f32: bool):
    return jax.default_matmul_precision("highest" if f32 else None)


def compare_rounds(label, ref_loss, loss, ref_params, params, f32):
    """Check one round against its reference (module constants)."""
    d_loss = abs(loss - ref_loss) / abs(ref_loss)
    d_par = max_abs_diff(params, ref_params)
    print(f"  {label}: round loss {ref_loss:.7f} vs {loss:.7f} (rel diff "
          f"{d_loss:.3e}); max |param diff| {d_par:.3e}")
    rtol = ROUND_F32_LOSS_RTOL if f32 else ROUND_LOSS_RTOL
    check(d_loss <= rtol, f"{label}: round loss agrees within rtol {rtol}")
    if f32:
        check(d_par <= ROUND_F32_PARAM_ATOL, f"{label}: parameters agree "
              f"within atol {ROUND_F32_PARAM_ATOL}")


def survey(d_embed: int):
    data = make_survey_data(SurveyConfig(d_embed=d_embed))
    train_groups, eval_groups = split_groups(data)
    return data, train_groups, eval_groups


def phase_train(gcfg, data, tr, ev):
    print(f"[train] FederatedGPO d_embed={gcfg.d_embed}, {len(tr)} clients, "
          f"{TRAIN_ROUNDS} rounds in blocks of {LOG_EVERY}", flush=True)
    fcfg = FedConfig(rounds=TRAIN_ROUNDS)
    check(fcfg.num_clients == len(tr), "paper client count matches split")
    fed = FederatedGPO(gcfg, fcfg, data, tr, ev)
    t0 = time.perf_counter()
    hist = fed.run(rounds=TRAIN_ROUNDS, log_every=LOG_EVERY)
    wall = time.perf_counter() - t0
    loss = np.asarray(hist.round_loss)
    first, last = loss[:LOG_EVERY].mean(), loss[-LOG_EVERY:].mean()
    print(f"  loss: first block {first:.5f} -> last block {last:.5f}; "
          f"eval AS={hist.eval_mean_as[-1]:.5f} FI={hist.eval_fi[-1]:.5f}")
    print(f"  info: {TRAIN_ROUNDS} rounds took {wall:.1f} s of wall-clock "
          "time, compilation included")
    check(len(loss) == TRAIN_ROUNDS and bool(np.isfinite(loss).all()),
          "every round loss is finite")
    check(last < first, "loss falls from the first block to the last")
    check(bool(np.isfinite(hist.eval_mean_as[-1])), "eval AS is finite")
    return fed.global_params


def phase_pallas(gcfg, data, tr, ev):
    def one_round(pallas):
        fcfg = FedConfig(rounds=1, use_pallas_attention=pallas,
                         use_pallas_aggregation=pallas)
        fed = FederatedGPO(gcfg, fcfg, data, tr, ev)
        return fed, fed.run(rounds=1).round_loss[0]

    print("[pallas] one round, Pallas attention + aggregation vs dense",
          flush=True)
    for f32 in (False, True):
        with precision(f32):
            (dense, l_dense), (fed, l_pallas) = (one_round(False),
                                                 one_round(True))
        if not f32:
            names = kernel_names(
                fed._block, fed.global_params, fed.opt_states, fed.ef_resid,
                fed.fault_state, fed.server_state, jax.random.PRNGKey(0),
                jnp.ones((1,), bool))
            print(f"  kernels in the round's program: {sorted(names)}")
            check({"_gpo_fwd_kernel", "_gpo_bwd_dq_kernel",
                   "_gpo_bwd_dkdv_kernel", "_fedavg_kernel"} <= names,
                  "attention fwd+bwd and fedavg_reduce lower to "
                  "tpu_custom_call")
        compare_rounds("f32 matmuls" if f32 else "as run", l_dense,
                       l_pallas, dense.global_params, fed.global_params, f32)


def phase_serve(params, gcfg, data, ev):
    print(f"[serve] PreferenceServer, {REQUESTS} requests at "
          f"hit_ratio={HIT_RATIO}", flush=True)
    a = data.num_options
    trace = make_request_trace(data, list(ev), num_requests=REQUESTS,
                               hit_ratio=HIT_RATIO, seed=7)
    direct = jax.jit(predict_preferences, static_argnums=(1, 5))

    def serve(mode):
        """Serve the trace; max |pred - direct predict_preferences|."""
        ref = {r.rid: np.asarray(direct(params, gcfg, r.ctx_x, r.ctx_y,
                                        r.tgt_x, a)) for r in trace}
        server = PreferenceServer(
            params, gcfg, ServeConfig(int8_weights=mode == "int8"),
            num_options=a)
        t0 = time.perf_counter()
        done = server.run_trace(trace)
        wall = time.perf_counter() - t0
        err = max(float(np.max(np.abs(c.pred - ref[c.rid]))) for c in done)
        hits = sum(c.cache_hit for c in done)
        print(f"  {mode}: {len(done)} completed in {len(server.batches)} "
              f"batches, {hits} prefix-cache hits, max |pred - direct| "
              f"{err:.3e}; info: {wall:.2f} s wall-clock, compilation "
              "included")
        check(len(done) == REQUESTS and server.stats.rejected == 0,
              f"{mode}: every request completed")
        check(all(np.allclose(c.pred.sum(-1), 1.0, atol=1e-5)
                  for c in done), f"{mode}: rows lie on the simplex")
        return server, err

    serve("f32")
    server, err = serve("int8")
    check(err <= SERVE_INT8_ATOL, "int8: predictions match "
          f"predict_preferences within atol {SERVE_INT8_ATOL}")
    m = ServeConfig().ctx_buckets[0]
    names = kernel_names(
        _prefill_batch, server.params, gcfg, ServeConfig().ctx_buckets[-1],
        jnp.zeros((1, m, gcfg.d_embed)), jnp.zeros((1, m)),
        jnp.full((1,), m, jnp.int32))
    check("_int8_matmul_kernel" in names,
          "int8 weights lower to the quant_matmul tpu_custom_call")
    with precision(True):
        _, err = serve("f32 matmuls")
    check(err <= SERVE_F32_ATOL, "f32 matmuls: predictions match "
          f"predict_preferences within atol {SERVE_F32_ATOL}")


def phase_four_chips(gcfg, data, tr, ev):
    devices = jax.devices()[:4]
    check(len(devices) == 4, "four devices are visible")
    if len(devices) < 4:
        return
    c = FOUR_CHIP_CLIENTS
    tr = np.asarray(tr[:c])
    mesh = make_mesh((4,), ("data",), devices=devices)
    spec = NamedSharding(mesh, P("data"))
    print(f"[four-chips] make_sharded_round, {c} clients on "
          f"{len(devices)} chips", flush=True)

    def sharded_vs_stacked(agg_name):
        fcfg = FedConfig(num_clients=c, rounds=1,
                         agg=AggConfig(name=agg_name))
        # reference: the stacked round on one device
        ref = FederatedGPO(gcfg, fcfg, data, tr, ev)
        ref_loss = ref.run(rounds=1).round_loss[0]
        # the same round, clients sharded over the mesh, same key chain
        params = init_gpo_params(gcfg, jax.random.PRNGKey(fcfg.seed))
        _, k_round, _ = jax.random.split(jax.random.PRNGKey(fcfg.seed + 1),
                                         3)
        _, k_train = jax.random.split(k_round)
        opt = adam(fcfg.lr)
        agg = make_aggregator(fcfg.agg, num_clients=c)
        server_state = agg.init(params)
        client_params = broadcast_to_clients(params, c)
        put = lambda t: jax.device_put(t, spec)  # noqa: E731
        args = (put(client_params),
                put(jax.vmap(opt.init)(client_params)),
                put(jax.random.split(k_train, c)),
                put(jnp.asarray(tr, jnp.int32)),
                put(normalize_weights(data.sizes[tr])),
                jax.device_put(server_state,
                               server_state_shardings(server_state, mesh)))
        round_fn = jax.jit(make_sharded_round(gcfg, fcfg, data, mesh,
                                              client_axes=("data",),
                                              opt=opt, agg=agg))
        t0 = time.perf_counter()
        out_params, _, losses, _ = jax.block_until_ready(round_fn(*args))
        print(f"  {agg_name}: info: sharded round "
              f"{time.perf_counter() - t0:.1f} s wall-clock, compilation "
              "included")
        return ref, ref_loss, out_params, float(jnp.mean(losses))

    for agg_name in ("fedavg", "median"):
        for f32 in (False, True):
            with precision(f32):
                ref, ref_loss, out_params, loss = sharded_vs_stacked(agg_name)
            if not f32:
                # each chip holds its own two clients' rows of every output
                placed = []
                for leaf in jax.tree.leaves(out_params):
                    rows = {s.device.id: (s.index[0].start, s.index[0].stop)
                            for s in leaf.addressable_shards}
                    placed.append(len(rows) == 4 and sorted(rows.values())
                                  == [(i * c // 4, (i + 1) * c // 4)
                                      for i in range(4)])
                check(all(placed), f"{agg_name}: client rows sharded "
                      f"{c // 4} per chip over 4 distinct devices")
            # every client row of the sharded output holds the new global
            rows = jax.tree.map(
                lambda g, o: jnp.broadcast_to(g[None], o.shape),
                ref.global_params, out_params)
            compare_rounds(
                f"{agg_name}, {'f32 matmuls' if f32 else 'as run'}",
                ref_loss, loss, rows, out_params, f32)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded round on four chips and its "
                         "one-device reference")
    args = ap.parse_args()

    device = device_info()
    if device["platform"] != "tpu":
        # on the CPU every Pallas kernel would run in interpret mode
        sys.exit(f"no TPU: JAX is on {device}")
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    gcfg = GPOConfig(d_embed=D_EMBED)
    data, tr, ev = survey(D_EMBED)
    if args.four_chips:
        phase_four_chips(gcfg, data, tr, ev)
    else:
        params = phase_train(gcfg, data, tr, ev)
        phase_pallas(gcfg, data, tr, ev)
        phase_serve(params, gcfg, data, ev)
    if _failures:
        print(f"{len(_failures)} check(s) failed: {_failures}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
