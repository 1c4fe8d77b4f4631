"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes WITHOUT allocating anything (params/batches/caches are
ShapeDtypeStructs).

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
      --shape train_4k [--multi-pod] [--out results.json]

Per pair this prints/records:
  * compiled.memory_analysis()  — proves the layout fits 16 GB/chip,
  * compiled.cost_analysis()    — per-chip FLOPs / bytes for §Roofline,
  * the collective schedule (op kind -> bytes) parsed from the HLO,
  * the three roofline terms + bottleneck + MODEL_FLOPS/HLO_FLOPs ratio.

The 2x16x16 multi-pod pass proves the 'pod' axis shards (hierarchical
FedAvg / data parallelism over DCI); the roofline table is single-pod.

``main`` forces 512 host-platform devices before JAX starts its backend;
importing this module (tests call ``lower_gpo_round``)
changes nothing in the importing process.
"""

import argparse
import os
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import INPUT_SHAPES, get_arch, override
from repro.core.trainer import make_prefill_step, make_serve_step, make_train_step
from repro.launch import roofline as rl
from repro.launch.mesh import data_axes, make_mesh, make_production_mesh
from repro.launch.sharding import (
    adafactor_state_shardings,
    adam_state_shardings,
    batch_shardings,
    cache_shardings,
    params_shardings,
)
from repro.launch.specs import (
    batch_specs,
    cache_specs,
    count_params,
    input_specs,
    params_specs,
    serving_config,
    train_settings,
)
from repro.models.partitioning import activation_sharding
from repro.optim import adafactor, adam


def _mem_stats(memory_analysis) -> dict:
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes")
    out = {}
    for k in keys:
        v = getattr(memory_analysis, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def lower_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
               fsdp_override: bool | None = None,
               cfg_overrides: dict | None = None,
               verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    num_chips = int(jnp.prod(jnp.asarray(list(mesh.shape.values()))))
    shape = INPUT_SHAPES[shape_name]
    cfg = override(get_arch(arch), param_dtype="bfloat16",
                   activation_dtype="bfloat16")
    cfg = serving_config(cfg, shape)
    if cfg_overrides:
        cfg = override(cfg, **cfg_overrides)
    settings = train_settings(cfg)
    fsdp = settings.fsdp if fsdp_override is None else fsdp_override
    baxes = data_axes(mesh)

    p_shapes = params_specs(cfg)
    p_shard = params_shardings(p_shapes, cfg, mesh, fsdp=fsdp)

    t0 = time.time()
    ctx = activation_sharding(mesh)
    ctx.__enter__()
    if shape.kind == "train":
        opt = adafactor(1e-3) if settings.optimizer == "adafactor" else adam(1e-3)
        opt_shapes = jax.eval_shape(opt.init, p_shapes)
        if settings.optimizer == "adafactor":
            o_shard = adafactor_state_shardings(p_shard, p_shapes, mesh)
        else:
            o_shard = adam_state_shardings(p_shard, mesh)
        b_shapes = batch_specs(cfg, shape, with_labels=True)
        b_shard = batch_shardings(b_shapes, mesh, baxes)
        step = make_train_step(cfg, opt, microbatch=settings.microbatch,
                               remat=settings.remat)
        jitted = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                         out_shardings=(p_shard, o_shard, None),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(p_shapes, opt_shapes, b_shapes)
    elif shape.kind == "prefill":
        b_shapes = batch_specs(cfg, shape, with_labels=False)
        b_shard = batch_shardings(b_shapes, mesh, baxes)
        c_shapes = cache_specs(cfg, shape)
        c_shard = cache_shardings(c_shapes, cfg, mesh, baxes)
        step = make_prefill_step(cfg, shape.seq_len)
        jitted = jax.jit(step, in_shardings=(p_shard, b_shard),
                         out_shardings=(None, c_shard))
        lowered = jitted.lower(p_shapes, b_shapes)
    else:  # decode
        c_shapes = cache_specs(cfg, shape)
        c_shard = cache_shardings(c_shapes, cfg, mesh, baxes)
        tok_spec = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        tok_shard = batch_shardings({"tokens": tok_spec}, mesh, baxes)["tokens"]
        pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
        pos_shard = NamedSharding(mesh, P())
        step = make_serve_step(cfg)
        jitted = jax.jit(step,
                         in_shardings=(p_shard, c_shard, tok_shard, pos_shard),
                         out_shardings=(None, c_shard),
                         donate_argnums=(1,))
        lowered = jitted.lower(p_shapes, c_shapes, tok_spec, pos_spec)
    ctx.__exit__(None, None, None)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # pre-0.5 jax wraps it in a list
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    mflops = rl.model_flops(cfg, shape, num_chips)
    roof = rl.analyze(cost, hlo, model_flops_per_chip=mflops)
    xla_flops = float(cost.get("flops", 0.0))  # while-body-once cross-check

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "x".join(str(v) for v in mesh.shape.values()),
        "multi_pod": multi_pod,
        "num_chips": num_chips,
        "params": count_params(cfg),
        "fsdp": fsdp,
        "kind": shape.kind,
        "optimizer": settings.optimizer if shape.kind == "train" else None,
        "microbatch": settings.microbatch if shape.kind == "train" else None,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": _mem_stats(mem),
        "roofline": roof.as_dict(),
        "xla_cost_flops": xla_flops,
    }
    if verbose:
        print(f"== {arch} x {shape_name} mesh={result['mesh']} ==")
        print("memory_analysis:", mem)
        print("cost_analysis: flops=%.3e bytes=%.3e" % (
            roof.flops_per_chip, roof.bytes_per_chip))
        print("collectives:", roof.collectives.bytes_by_kind)
        print("roofline: compute=%.2fms memory=%.2fms collective=%.2fms "
              "-> %s | useful=%.2f" % (
                  roof.compute_s * 1e3, roof.memory_s * 1e3,
                  roof.collective_s * 1e3, roof.bottleneck,
                  roof.useful_ratio))
    return result


def lower_gpo_round(agg_name: str, *, clients: int = 8,
                    edges: int = 1,
                    use_pallas: bool = False,
                    use_pallas_attention: bool = False,
                    clip_norm: float = 0.0,
                    noise_multiplier: float = 0.0,
                    compress: str = "none",
                    topk_frac: float = 0.01,
                    faults: bool = False,
                    attack: str = "none",
                    attackers: int = 0,
                    norm_bound: float = 0.0,
                    verbose: bool = True) -> dict:
    """Compile the shard_map federated GPO round for one aggregation
    strategy on a ``clients``-device 'data' mesh and report its
    collective schedule (DESIGN.md §7): linear strategies must show ONE
    parameter-sized all-reduce (the weighted delta psum); the robust
    strategies an all-gather of the flat client-delta matrix instead.
    ``use_pallas_attention`` routes every local epoch's fwd+bwd through
    the banded custom-VJP attention kernels (DESIGN.md §8) so the
    compiled schedule reflects the fused training hot path.
    ``clip_norm`` > 0 compiles the DP client-delta pipeline
    (DESIGN.md §9): clip + noise happen shard-locally BEFORE the
    collectives, so the schedule must keep the exact same shape — one
    psum of the (already privatized) weighted delta for the linear
    family, an all-gather of the privatized matrix for the robust one.
    ``compress`` compiles the delta codec (DESIGN.md §10): for the
    robust family under ``int8`` the flat-delta all-gather turns into
    an int8-payload + f32-scale all-gather (~4x fewer bytes — the
    reported byte counts, parsed both flat from the HLO text and
    trip-count-aware via ``launch/hlo_cost.py``, prove it); the linear
    family dequantizes shard-locally and keeps its one f32 psum.
    ``faults`` compiles the fault-aware round (DESIGN.md §11): the
    failure schedule is derived replicated from the fault key and
    survivor weights are zeroed/renormalized shard-locally, so the
    linear family's collective schedule must keep the SAME single
    parameter-sized psum — tests/test_availability.py pins the byte
    counts equal to the fault-free round.
    ``edges`` > 1 compiles the §14 two-level client→edge→server round
    on an (edges, clients/edges) ('edge', 'data') mesh: the robust
    family's flat all-gather splits into an intra-edge hop (C/E rows)
    plus a cross-edge hop of only E candidate rows (int8 when
    ``compress="int8"``) — the per-op ``collective_ops`` entry makes the
    two hops individually visible — while the linear family keeps its
    one psum over both axes."""
    from jax.sharding import NamedSharding
    from repro.configs import (AdversaryConfig, AggConfig,
                               AvailabilityConfig, CompressionConfig,
                               FedConfig, GPOConfig, HierarchyConfig,
                               PrivacyConfig)
    from repro.core import make_aggregator
    from repro.core.availability import init_fault_state
    from repro.core.federated import make_sharded_round
    from repro.core.gpo import init_gpo_params
    from repro.data import SurveyConfig, make_survey_data
    from repro.launch import hlo_cost
    from repro.launch.sharding import (fault_state_shardings,
                                       server_state_shardings)
    from repro.optim import adam
    from repro.utils.pytree import tree_count_params

    if edges > 1:
        # §14 two-level edge mesh: one client per device, E edge shards
        mesh = make_mesh((edges, clients // edges), ("edge", "data"))
        caxes = ("edge", "data")
    else:
        mesh = make_mesh((clients,), ("data",))
        caxes = ("data",)
    data = make_survey_data(SurveyConfig(num_groups=clients,
                                         num_questions=30, d_embed=16,
                                         seed=0))
    gcfg = GPOConfig(d_embed=16, d_model=32, num_layers=1, num_heads=2,
                     d_ff=32)
    privacy = PrivacyConfig(clip_norm=clip_norm,
                            noise_multiplier=noise_multiplier)
    compression = CompressionConfig(kind=compress, topk_frac=topk_frac)
    avail = (AvailabilityConfig(online_prob=0.8, crash_prob=0.05,
                                straggler_prob=0.1, max_staleness=4)
             if faults else AvailabilityConfig())
    adversary = AdversaryConfig(kind=attack, num_attackers=attackers)
    fcfg = FedConfig(num_clients=clients, local_epochs=2, num_context=6,
                     num_target=6,
                     agg=AggConfig(name=agg_name,
                                   num_malicious=attackers,
                                   norm_bound=norm_bound),
                     use_pallas_aggregation=use_pallas,
                     use_pallas_attention=use_pallas_attention,
                     privacy=privacy, compression=compression,
                     avail=avail, adversary=adversary,
                     hierarchy=HierarchyConfig(num_edges=edges))
    opt = adam(fcfg.lr)
    agg = make_aggregator(fcfg.agg, num_clients=clients,
                          use_pallas=use_pallas)
    params = init_gpo_params(gcfg, jax.random.PRNGKey(0))
    server_state = agg.init(params)
    round_fn = make_sharded_round(gcfg, fcfg, data, mesh,
                                  client_axes=caxes, opt=opt, agg=agg)

    spec = NamedSharding(mesh, P(caxes if len(caxes) > 1 else caxes[0]))
    shard = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(
            (clients,) + tuple(x.shape), x.dtype, sharding=spec), t)
    cp = shard(params)
    opt_s = shard(opt.init(params))
    keys = jax.ShapeDtypeStruct((clients, 2), jnp.uint32, sharding=spec)
    gids = jax.ShapeDtypeStruct((clients,), jnp.int32, sharding=spec)
    repl = NamedSharding(mesh, P())
    # fault mode: weights arrive replicated — every shard renormalizes
    # the survivor mass redundantly (DESIGN.md §11)
    w = jax.ShapeDtypeStruct((clients,), jnp.float32,
                             sharding=repl if faults else spec)
    srv = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(tuple(x.shape), x.dtype,
                                          sharding=s),
        server_state, server_state_shardings(server_state, mesh))
    args = (cp, opt_s, keys, gids, w, srv)
    if faults:
        fault0 = init_fault_state(clients, tree_count_params(params))
        f_shard = fault_state_shardings(mesh, caxes)
        fault = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(tuple(x.shape), x.dtype,
                                              sharding=s),
            fault0, f_shard)
        fkey = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl)
        args += (fault, fkey)
    if compression.enabled and compression.error_feedback:
        args += (jax.ShapeDtypeStruct(
            (clients, tree_count_params(params)), jnp.float32,
            sharding=spec),)
    if adversary.enabled:
        # replicated Byzantine key, LAST (after the EF residual) per the
        # round's trailing-arg order
        args += (jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl),)

    t0 = time.time()
    lowered = jax.jit(round_fn).lower(*args)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    coll = rl.parse_collectives(hlo)
    # trip-count-aware cross-check: collectives inside while loops count
    # once per iteration in hlo_cost's walk (DESIGN.md §6)
    cost_totals = hlo_cost.analyze_hlo(hlo)
    cost_coll = cost_totals.collective_bytes
    result = {
        "agg": agg_name,
        "clients": clients,
        "edges": edges,
        "use_pallas_aggregation": use_pallas,
        "use_pallas_attention": use_pallas_attention,
        "private": privacy.enabled,
        "clip_norm": clip_norm,
        "noise_multiplier": noise_multiplier,
        "compress": compress,
        "topk_frac": topk_frac if compress == "topk" else None,
        "faults": faults,
        "attack": attack,
        "attackers": attackers,
        "norm_bound": norm_bound,
        "linear": agg.linear,
        "compile_s": round(time.time() - t0, 1),
        "collective_bytes_by_kind": dict(coll.bytes_by_kind),
        "collective_count_by_kind": dict(coll.count_by_kind),
        "collective_count": coll.total_count,
        "hlo_cost_collective_bytes_by_kind": {
            k: float(v) for k, v in cost_coll.items()},
        # per-op collective detail (kind, bytes, trip multiplier): makes
        # the §14 two-hop schedule individually visible — the intra-edge
        # and cross-edge all-gathers land as separate entries
        "collective_ops": [[k, float(b), float(m)]
                           for k, b, m in cost_totals.collective_ops],
        "memory": _mem_stats(compiled.memory_analysis()),
    }
    if verbose:
        print(f"== gpo-fed round x agg={agg_name} mesh={clients}"
              + (f" edges={edges}" if edges > 1 else "")
              + (f" compress={compress}" if compress != "none" else "")
              + (" faults" if faults else "")
              + (f" attack={attack}({attackers})" if attack != "none"
                 else "")
              + (f" norm_bound={norm_bound}" if norm_bound else "")
              + " ==")
        print("collectives:", result["collective_bytes_by_kind"])
        print("collectives (hlo_cost, trip-aware):",
              result["hlo_cost_collective_bytes_by_kind"])
    return result


def main() -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=sorted(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--gpo-fed", action="store_true",
                    help="lower the shard_map federated GPO round instead "
                         "of a backbone (arch/shape ignored)")
    ap.add_argument("--agg", default="fedavg",
                    help="aggregation strategy for --gpo-fed")
    ap.add_argument("--clients", type=int, default=8,
                    help="client-mesh size for --gpo-fed")
    ap.add_argument("--edges", type=int, default=1,
                    help="edge shards for the §14 two-level "
                         "client→edge→server round (must divide "
                         "--clients; 1 = flat)")
    ap.add_argument("--pallas-attn", action="store_true",
                    help="route --gpo-fed local training through the "
                         "banded custom-VJP attention kernels")
    ap.add_argument("--private", action="store_true",
                    help="compile the --gpo-fed round with the DP "
                         "client-delta pipeline (shard-local clip+noise "
                         "before the round's collectives, DESIGN.md §9)")
    ap.add_argument("--clip-norm", type=float, default=1.0,
                    help="per-client L2 clip for --private")
    ap.add_argument("--noise-multiplier", type=float, default=1.0,
                    help="Gaussian noise multiplier for --private")
    ap.add_argument("--compress", default="none",
                    choices=["none", "int8", "topk"],
                    help="compile the --gpo-fed round with the delta "
                         "codec (DESIGN.md §10): robust strategies "
                         "all-gather int8 payloads + f32 scales instead "
                         "of f32 vectors")
    ap.add_argument("--topk-frac", type=float, default=0.01,
                    help="fraction of coordinates kept for "
                         "--compress topk")
    ap.add_argument("--faults", action="store_true",
                    help="compile the --gpo-fed round with the fault-"
                         "injection layer (DESIGN.md §11): replicated "
                         "failure schedule, masked survivor weights — "
                         "the linear family must keep its ONE psum")
    ap.add_argument("--attack", default="none",
                    choices=["none", "sign_flip", "scaled", "gaussian",
                             "alie", "label_flip"],
                    help="compile the --gpo-fed round with the Byzantine "
                         "attack stage (DESIGN.md §13); linear family "
                         "keeps its collective schedule byte-identical")
    ap.add_argument("--attackers", type=int, default=2,
                    help="Byzantine clients per round for --attack")
    ap.add_argument("--norm-bound", type=float, default=0.0,
                    help="server-side L2 norm bound on received rows "
                         "(0 = off)")
    ap.add_argument("--out", default=None, help="append result as json line")
    args = ap.parse_args()
    if not args.gpo_fed and not (args.arch and args.shape):
        ap.error("--arch and --shape are required unless --gpo-fed")
    what = (f"gpo-fed x {args.agg} clients={args.clients}"
            + (" private" if args.private else "")
            + (f" compress={args.compress}" if args.compress != "none"
               else "")
            + (" faults" if args.faults else "")
            + (f" attack={args.attack}" if args.attack != "none"
               else "") if args.gpo_fed
            else f"{args.arch} x {args.shape} multi_pod={args.multi_pod}")
    try:
        if args.gpo_fed:
            result = lower_gpo_round(
                args.agg, clients=args.clients, edges=args.edges,
                use_pallas_attention=args.pallas_attn,
                clip_norm=args.clip_norm if args.private else 0.0,
                noise_multiplier=(args.noise_multiplier if args.private
                                  else 0.0),
                compress=args.compress, topk_frac=args.topk_frac,
                faults=args.faults,
                attack=args.attack,
                attackers=args.attackers if args.attack != "none" else 0,
                norm_bound=args.norm_bound)
        else:
            result = lower_pair(args.arch, args.shape,
                                multi_pod=args.multi_pod)
        status = "ok"
    except Exception:
        traceback.print_exc()
        result = {"arch": args.arch, "shape": args.shape,
                  "gpo_fed": args.gpo_fed,
                  "multi_pod": args.multi_pod, "error": traceback.format_exc()}
        status = "error"
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(result) + "\n")
    print(f"DRYRUN {status}: {what}")
    if status == "error":
        raise SystemExit(1)


if __name__ == "__main__":
    main()
