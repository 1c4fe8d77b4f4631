"""Training launcher.

Three trainer modes, all runnable on CPU with --smoke (reduced configs):

  standard  — plain LM training of the selected architecture.
  fedavg    — the paper's technique on the backbone: C clients run local
              LM steps on disjoint data shards; rounds end with Eq. 3
              weighted parameter averaging.
  fedlora   — frozen backbone, federated LoRA adapters (the large-arch
              recipe).
  gpo       — the paper's own experiment: federated GPO preference
              predictor on synthetic survey data (see
              examples/federated_vs_centralized.py for the paper's
              federated-vs-centralized comparison).

All federated trainers take ``--agg`` (plus the matching hyperparameter
flags) to select the server-aggregation strategy from the registry in
``repro.core.aggregation`` (DESIGN.md §7), ``--clip-norm`` /
``--noise-multiplier`` / ``--dp-delta`` to run the differentially-
private client-delta pipeline (DESIGN.md §9; per-round ε is reported
from the Rényi accountant), and ``--compress`` / ``--topk-frac`` /
``--no-error-feedback`` to compress the client→server deltas (int8
stochastic quantization or top-k sparsification with an EF21 residual,
DESIGN.md §10 — applied AFTER the DP release, so ε is unchanged).

The first line printed names the device JAX chose. To require the chip,
set ``JAX_PLATFORMS=tpu``: JAX then fails at start-up instead of falling
back to the CPU, where the Pallas kernels would run in interpret mode.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --trainer fedavg --rounds 3 --local-steps 2 --agg fedavgm
  PYTHONPATH=src python -m repro.launch.train --trainer gpo --rounds 50 \
      --agg adaptive
  PYTHONPATH=src python -m repro.launch.train --trainer gpo --rounds 50 \
      --clip-norm 0.5 --noise-multiplier 0.8
  PYTHONPATH=src python -m repro.launch.train --trainer gpo --rounds 50 \
      --compress int8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import (
    AdversaryConfig,
    AggConfig,
    CompressionConfig,
    FedConfig,
    GPOConfig,
    INPUT_SHAPES,
    PrivacyConfig,
    get_arch,
    smoke_variant,
)
from repro.core.privacy import make_accountant
from repro.core import (
    AGGREGATORS,
    FederatedGPO,
    broadcast_to_clients,
    init_lora,
    make_aggregator,
    make_backbone_fedavg_round,
    make_fedlora_round,
    make_train_step,
    normalize_weights,
)
from repro.data import LMDataConfig, make_survey_data, SurveyConfig, split_groups
from repro.data.lm_data import synthetic_lm_batches
from repro.models import init_params
from repro.optim import adam
from repro.utils.pytree import tree_count_params
from repro.utils.runtime import device_info, enable_compile_cache


def _stack_client_batches(it, clients: int, steps: int):
    batches = [[next(it) for _ in range(steps)] for _ in range(clients)]
    per_client = [
        jax.tree.map(lambda *xs: jnp.stack(xs), *bs) for bs in batches]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_client)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--trainer", default="standard",
                    choices=["standard", "fedavg", "fedlora", "gpo"])
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    # server-aggregation strategy (DESIGN.md §7); applies to the gpo,
    # fedavg, and fedlora trainers
    ap.add_argument("--agg", default="fedavg", choices=AGGREGATORS.names())
    ap.add_argument("--server-lr", type=float, default=1.0)
    ap.add_argument("--server-momentum", type=float, default=0.9,
                    help="fedavgm server momentum")
    ap.add_argument("--prox-mu", type=float, default=0.0,
                    help="FedProx client proximal coefficient (gpo trainer)")
    ap.add_argument("--trim-frac", type=float, default=0.1,
                    help="trimmed_mean per-side trim fraction")
    ap.add_argument("--fair-temp", type=float, default=1.0,
                    help="adaptive fairness-weight temperature")
    # DP client-delta pipeline (DESIGN.md §9); applies to every
    # federated trainer. --clip-norm 0 (default) disables it.
    ap.add_argument("--clip-norm", type=float, default=0.0,
                    help="per-client L2 clip on the flat delta (0 = off)")
    ap.add_argument("--noise-multiplier", type=float, default=0.0,
                    help="Gaussian noise std = z * clip-norm per client")
    ap.add_argument("--dp-delta", type=float, default=1e-5,
                    help="target delta for the Renyi accountant's eps")
    # client->server delta compression (DESIGN.md §10); applies to every
    # federated trainer. --compress none (default) disables it.
    ap.add_argument("--compress", default="none",
                    choices=["none", "int8", "topk"],
                    help="delta codec: int8 stochastic quantization or "
                         "top-k magnitude sparsification")
    ap.add_argument("--topk-frac", type=float, default=0.01,
                    help="fraction of coordinates kept per client "
                         "(--compress topk)")
    ap.add_argument("--no-error-feedback", action="store_true",
                    help="disable the EF21 error-feedback residual")
    # Byzantine attack simulation + defenses (DESIGN.md §13). --attack
    # none (default) disables the stage; pick a defense with --agg
    # krum/multi_krum/geomedian/median and/or --norm-bound.
    ap.add_argument("--attack", default="none",
                    choices=["none", "sign_flip", "scaled", "gaussian",
                             "alie", "label_flip"],
                    help="per-round Byzantine client attack (label_flip "
                         "is gpo-only)")
    ap.add_argument("--attackers", type=int, default=0,
                    help="number of Byzantine clients per round (also "
                         "the defenses' assumed f)")
    ap.add_argument("--attack-scale", type=float, default=10.0,
                    help="model-replacement factor for --attack scaled")
    ap.add_argument("--norm-bound", type=float, default=0.0,
                    help="server-side per-client L2 norm bound on "
                         "received deltas (0 = off)")
    ap.add_argument("--multi-krum-m", type=int, default=3,
                    help="rows averaged by --agg multi_krum")
    args = ap.parse_args()
    print("device:", device_info())
    enable_compile_cache()

    agg_cfg = AggConfig(name=args.agg, server_lr=args.server_lr,
                        momentum=args.server_momentum,
                        prox_mu=args.prox_mu, trim_frac=args.trim_frac,
                        fair_temp=args.fair_temp,
                        num_malicious=args.attackers,
                        multi_krum_m=args.multi_krum_m,
                        norm_bound=args.norm_bound)
    adv_cfg = AdversaryConfig(kind=args.attack,
                              num_attackers=args.attackers,
                              scale=args.attack_scale)
    adv_cfg.validate()
    priv_cfg = PrivacyConfig(clip_norm=args.clip_norm,
                             noise_multiplier=args.noise_multiplier,
                             target_delta=args.dp_delta)
    priv_cfg.validate()
    comp_cfg = CompressionConfig(kind=args.compress,
                                 topk_frac=args.topk_frac,
                                 error_feedback=not args.no_error_feedback)
    comp_cfg.validate()

    if args.trainer == "gpo":
        data = make_survey_data(SurveyConfig(seed=args.seed))
        tr, ev = split_groups(data, seed=args.seed)
        gcfg = GPOConfig(d_embed=data.phi.shape[-1])
        fcfg = FedConfig(num_clients=len(tr), rounds=args.rounds,
                         seed=args.seed, agg=agg_cfg, privacy=priv_cfg,
                         compression=comp_cfg, adversary=adv_cfg)
        fed = FederatedGPO(gcfg, fcfg, data, tr, ev)
        hist = fed.run(rounds=args.rounds, log_every=10)
        print(f"final loss={hist.round_loss[-1]:.4f} "
              f"AS={hist.eval_mean_as[-1]:.4f} FI={hist.eval_fi[-1]:.4f}")
        if hist.round_eps:
            print(f"privacy: eps={hist.round_eps[-1]:.3f} at "
                  f"delta={priv_cfg.target_delta:g} after {args.rounds} "
                  f"rounds (clip={priv_cfg.clip_norm}, "
                  f"z={priv_cfg.noise_multiplier})")
        if args.ckpt_dir:
            save_checkpoint(args.ckpt_dir, args.rounds, fed.global_params)
        return

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    opt = adam(args.lr)
    data_cfg = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                            global_batch=args.batch, seed=args.seed)
    it = synthetic_lm_batches(data_cfg)

    if args.trainer == "standard":
        step = jax.jit(make_train_step(cfg, opt))
        opt_state = opt.init(params)
        t0 = time.time()
        for i in range(args.steps):
            params, opt_state, m = step(params, opt_state, next(it))
            if i % max(1, args.steps // 10) == 0:
                print(f"step {i:4d} loss={float(m['loss']):.4f}")
        print(f"done: {args.steps} steps in {time.time()-t0:.1f}s "
              f"final loss={float(m['loss']):.4f}")
    else:
        c = args.clients
        weights = normalize_weights(jnp.ones((c,)))
        agg = make_aggregator(agg_cfg, num_clients=c)
        if args.trainer == "fedavg":
            client_params = broadcast_to_clients(params, c)
            opt_states = jax.vmap(opt.init)(client_params)
            rnd = jax.jit(make_backbone_fedavg_round(
                cfg, opt, args.local_steps, agg=agg, privacy=priv_cfg,
                compression=comp_cfg, adversary=adv_cfg))
            server_state = agg.init(params)
            payload = params
        else:
            lora = init_lora(params, key, rank=8)
            client_params = broadcast_to_clients(lora, c)
            opt_states = jax.vmap(opt.init)(client_params)
            rnd = jax.jit(make_fedlora_round(
                cfg, params, opt, args.local_steps, agg=agg,
                privacy=priv_cfg, compression=comp_cfg,
                adversary=adv_cfg))
            server_state = agg.init(lora)
            payload = lora
        # full participation => sampling rate 1 for the accountant
        accountant = make_accountant(priv_cfg, 1.0)
        noise_base = jax.random.PRNGKey(args.seed + 17)
        # EF residual (DESIGN.md §10): one flat f32 row per client
        ef = comp_cfg.enabled and comp_cfg.error_feedback
        # trailing-arg contract of _aggregated_round: [resid][, round_key]
        need_key = (priv_cfg.enabled
                    or (comp_cfg.enabled and comp_cfg.needs_rng)
                    or adv_cfg.enabled)
        resid = (jnp.zeros((c, tree_count_params(payload)), jnp.float32)
                 if ef else None)
        for r in range(args.rounds):
            batches = _stack_client_batches(it, c, args.local_steps)
            round_args = (client_params, opt_states, batches, weights,
                          server_state)
            if ef:
                round_args += (resid,)
            if need_key:
                round_args += (jax.random.fold_in(noise_base, r),)
            out = rnd(*round_args)
            client_params, opt_states, losses, server_state = out[:4]
            if ef:
                resid = out[4]
            eps = (f" eps={accountant.epsilon(r + 1):.3f}"
                   if accountant else "")
            print(f"round {r:3d} client losses="
                  f"{np.round(np.asarray(losses), 4)}{eps}")
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.steps,
                        params if args.trainer == "standard"
                        else client_params)


if __name__ == "__main__":
    main()
