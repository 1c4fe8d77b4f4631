"""Serving launcher: batched greedy decoding against a prefilled KV cache,
or the GPO preference-serving engine (the paper's inference product).

The GPO path trains once and checkpoints the predictor (repro.checkpoint);
``--restore`` serves the latest checkpoint from ``--ckpt-dir`` instead of
retraining, which is the actual serving contract — the trained preference
model is the product, not the training loop. Requests flow through
``core.serving.PreferenceServer`` (DESIGN.md §12): admission-controlled
queue, bucketed continuous batching, LRU prefix/KV cache over shared ICL
contexts, and optional int8 weights (``--int8``).

The first line printed names the device JAX chose. To require the chip,
set ``JAX_PLATFORMS=tpu``: JAX then fails at start-up instead of falling
back to the CPU, where the Pallas kernels would run in interpret mode.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --prompt-len 16 --gen-len 16 --batch 4
  PYTHONPATH=src python -m repro.launch.serve --gpo --requests 64
  PYTHONPATH=src python -m repro.launch.serve --gpo --restore --int8 \
      --requests 64 --hit-ratio 0.75
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (
    latest_checkpoint,
    restore_checkpoint,
    save_checkpoint,
)
from repro.configs import (
    AggConfig,
    FedConfig,
    GPOConfig,
    ServeConfig,
    get_arch,
    smoke_variant,
)
from repro.core import (
    FederatedGPO,
    PreferenceServer,
    greedy_decode,
    init_gpo_params,
    latency_summary,
    make_prefill_step,
    make_request_trace,
)
from repro.data import SurveyConfig, make_survey_data, split_groups
from repro.models import init_params
from repro.utils.runtime import device_info, enable_compile_cache


def serve_lm(args) -> None:
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    b, p = args.batch, args.prompt_len
    total = p + args.gen_len
    prompts = jax.random.randint(key, (b, p), 0, cfg.vocab_size)
    kw = {}
    if cfg.is_encoder_decoder:
        kw["enc_embeds"] = jax.random.normal(
            key, (b, cfg.enc_seq_len, cfg.d_model))
    prefill = jax.jit(lambda pr, batch: make_prefill_step(cfg, total)(
        pr, batch))
    t0 = time.time()
    last_logits, cache = prefill(params, {"tokens": prompts, **kw})
    first = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)[:, None]
    toks, _ = greedy_decode(cfg, params, cache, first, p, args.gen_len - 1)
    toks = np.asarray(jnp.concatenate([first, toks], axis=1))
    dt = time.time() - t0
    print(f"arch={cfg.name} batch={b} prompt={p} generated={args.gen_len}")
    print(f"tokens/s={b * args.gen_len / dt:.1f}")
    for i in range(min(b, 4)):
        print(f"  seq{i}: {toks[i].tolist()}")


def _restore_params(ckpt_dir: str, gcfg: GPOConfig, seed: int) -> dict:
    """Load the latest GPO checkpoint or fail with an actionable error
    (never a raw stack trace): missing checkpoint, torn/corrupt file, and
    architecture mismatch each get their own message."""
    path = latest_checkpoint(ckpt_dir)
    if path is None:
        raise SystemExit(
            f"--restore: no checkpoint under {ckpt_dir!r}; run "
            "once without --restore to train and save one")
    like = init_gpo_params(gcfg, jax.random.PRNGKey(seed))
    try:
        params = restore_checkpoint(path, like)
    except (OSError, ValueError, KeyError) as e:
        raise SystemExit(
            f"--restore: checkpoint {path!r} is unreadable or does not "
            f"match the GPO architecture ({type(e).__name__}: {e}); "
            "delete it and retrain, or point --ckpt-dir at a checkpoint "
            "saved by this launcher") from e
    print(f"restored GPO predictor from {path}")
    return params


def serve_gpo(args) -> None:
    """Preference serving for unseen groups — the aligned-LLM reward-model
    path the paper proposes (§5), through the multi-tenant engine
    (DESIGN.md §12). Trains once and checkpoints; ``--restore`` loads the
    latest checkpoint instead."""
    data = make_survey_data(SurveyConfig(seed=args.seed))
    tr, ev = split_groups(data, seed=args.seed)
    gcfg = GPOConfig(d_embed=data.phi.shape[-1])
    if args.restore:
        params = _restore_params(args.ckpt_dir, gcfg, args.seed)
    else:
        fcfg = FedConfig(num_clients=len(tr), rounds=args.rounds,
                         seed=args.seed,
                         agg=AggConfig(name=args.agg, prox_mu=args.prox_mu))
        fed = FederatedGPO(gcfg, fcfg, data, tr, ev)
        print(f"training federated GPO for {args.rounds} rounds ...")
        fed.run(rounds=args.rounds)
        params = fed.global_params
        path = save_checkpoint(
            args.ckpt_dir, args.rounds, params,
            metadata={"rounds": args.rounds, "seed": args.seed,
                      "agg": args.agg, "d_embed": gcfg.d_embed})
        print(f"saved GPO predictor to {path} (serve with --restore)")

    scfg = ServeConfig(max_batch=args.max_batch,
                       int8_weights=args.int8)
    server = PreferenceServer(params, gcfg, scfg,
                              num_options=data.num_options)
    trace = make_request_trace(
        data, list(ev), num_requests=args.requests,
        hit_ratio=args.hit_ratio, rate=args.rate, seed=args.seed + 7)
    # warm up the jit shape family before timing: compile time is a
    # one-time cost, not per-request serving latency.
    t0 = time.time()
    server.run_trace(trace[: min(len(trace), scfg.max_batch)])
    t_compile = time.time() - t0
    server.reset(clear_cache=True)
    t0 = time.time()
    results = server.run_trace(trace)
    wall = time.time() - t0
    summary = latency_summary(results, wall)
    mode = "int8" if args.int8 else "f32"
    print(f"compile+first-call: {t_compile*1e3:.1f}ms (one-time)")
    print(f"served {summary['completed']}/{args.requests} requests "
          f"({mode}) in {wall*1e3:.1f}ms over {len(server.batches)} "
          f"batches; rejected={server.stats.rejected}")
    print(f"  p50={summary['p50_ms']:.2f}ms p99={summary['p99_ms']:.2f}ms "
          f"qps={summary['qps']:.1f} "
          f"prefix-cache hit-rate={summary['hit_rate']:.2f}")
    from repro.core.fairness import alignment_score

    for c in results[: min(4, len(results))]:
        req = trace[c.rid]
        truth = np.asarray(data.prefs)[req.meta["group"], req.meta["tgt_q"]]
        score = float(alignment_score(jnp.asarray(c.pred),
                                      jnp.asarray(truth)))
        print(f"  rid={c.rid} group={req.meta['group']} AS={score:.4f} "
              f"hit={c.cache_hit} "
              f"pred[0]={np.round(c.pred[0], 3).tolist()}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--gpo", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="checkpoints/gpo_serve")
    ap.add_argument("--restore", action="store_true",
                    help="load the latest GPO checkpoint instead of "
                         "retraining (gpo mode)")
    ap.add_argument("--agg", default="fedavg",
                    help="server-aggregation strategy for the training "
                         "path (DESIGN.md §7)")
    ap.add_argument("--prox-mu", type=float, default=0.0,
                    help="FedProx proximal coefficient (required > 0 for "
                         "--agg fedprox to differ from fedavg)")
    ap.add_argument("--requests", type=int, default=32,
                    help="gpo mode: number of requests in the load trace")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="gpo mode: engine batch cap per decode dispatch")
    ap.add_argument("--hit-ratio", type=float, default=0.5,
                    help="gpo mode: fraction of requests sharing an "
                         "already-seen ICL prefix (prefix-cache pressure)")
    ap.add_argument("--rate", type=float, default=None,
                    help="gpo mode: offered request rate in req/s "
                         "(default: all arrive at t=0, saturation)")
    ap.add_argument("--int8", action="store_true",
                    help="gpo mode: quantize weights to int8 at load "
                         "time and serve through the fused int8 kernel "
                         "(DESIGN.md §12)")
    args = ap.parse_args()
    print("device:", device_info())
    enable_compile_cache()
    if args.gpo:
        serve_gpo(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
