"""The composable round-stage pipeline (DESIGN.md §13).

Every federated round in this repo is the same five declared stages:

    [local_train, attack, privacy, codec, aggregate]

Historically each engine hand-wired its own copy of that sequence —
``FederatedGPO.round_step`` (stacked, subsampled), its fault-aware
sibling, ``make_sharded_round``'s two bodies (shard_map), and the
backbone/LoRA trainers' three ``round_fn`` variants in
``core/trainer.py``. ``RoundPipeline`` is the one assembly point: the
engines keep what is genuinely theirs (client layout, subsampling,
fault masking, collectives placement) and delegate the stage sequence —
including every enable/disable branch — to the methods here.

Stage contract:

* **local_train** stays in the engine (it owns vmap/shard_map layout
  and the optimizer carry). The pipeline's contribution is
  ``attacked_flags`` — the per-row poison mask a data-level attack
  (``kind="label_flip"``) feeds into ``_make_local_train``.
* **attack** (``attack_rows``) corrupts Byzantine rows of the raw flat
  (rows, P) delta matrix — before the privacy release, because a
  malicious client controls what it ships, not what the server does
  with it. Benign default: the stage is the Python-level identity.
* **privacy** then **codec** (``release_rows`` and the fused forms
  inside ``reduce_apply``/``sharded_delta``): DP clip+noise is the
  release point, the int8/top-k codec is post-processing of the
  released value (ε untouched), EF residual is carry state owned by
  the engine.
* **aggregate**: server-side ``norm_bound`` row clipping (the defense
  composable with every linear strategy) followed by the configured
  ``ServerAggregator`` reduce + apply. The fault-aware engines blend
  fresh/buffered rows first and call ``masked_reduce``.

Carry ownership: the pipeline is STATELESS config. Engines own and
thread every carry (opt states, server state, EF residual, fault
state); pipeline methods take them as explicit arguments and return the
updated values, which is what lets the same object serve a
``lax.scan`` body, a per-round jit, and a shard_map body.

Bit-equality discipline: with the attack stage off and
``norm_bound == 0`` every method below reproduces the pre-§13 engines'
dispatch VERBATIM (same ops, same order, same collectives) — the
attack-off traces are byte-pinned by tests/test_adversary.py and the
§9/§10/§11 pins keep riding. Enabling an attack or a norm bound
switches (statically) to a row-structured path that materializes the
per-client released rows between the stages.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (
    AdversaryConfig,
    CompressionConfig,
    HierarchyConfig,
    PrivacyConfig,
)
from repro.core import adversary as byz
from repro.core import availability as av
from repro.core import compression as cx
from repro.core import privacy as dp
from repro.core.aggregation import ServerAggregator
from repro.core.fedavg import fedavg_allreduce
from repro.kernels import fedavg_reduce
from repro.utils.pytree import (
    tree_ravel_clients,
    tree_unflatten_from_vector,
)

PyTree = Any

# the declared stage sequence every engine assembles (DESIGN.md §13)
STAGE_NAMES = ("local_train", "attack", "privacy", "codec", "aggregate")


@dataclass(frozen=True)
class RoundPipeline:
    """Stateless assembly of the five round stages for one FedConfig.

    ``num_clients`` is the FULL training population (attacker schedules
    draw over it; subsampled/sharded rows index into it via ``gids``).
    ``None`` means "rows are the population" — the backbone/LoRA
    trainers, which run full participation with no global id space.
    """

    adversary: AdversaryConfig
    privacy: PrivacyConfig
    compression: CompressionConfig
    agg: ServerAggregator
    num_clients: Optional[int] = None
    use_pallas: bool = False
    # two-level client→edge→server topology for the aggregate stage
    # (DESIGN.md §14); the default (num_edges=1) is statically disabled
    hierarchy: HierarchyConfig = HierarchyConfig()

    # -- static structure --------------------------------------------------
    @property
    def attack_delta(self) -> bool:
        """Delta-level attack configured (stage 2 active on the wire)."""
        return self.adversary.enabled and not self.adversary.data_level

    @property
    def flip_data(self) -> bool:
        """Data-level poisoning configured (stage 2 rides local_train)."""
        return self.adversary.enabled and self.adversary.data_level

    @property
    def norm_bound(self) -> float:
        return self.agg.cfg.norm_bound

    @property
    def restructured(self) -> bool:
        """True when the round must materialize per-client released rows
        (an active delta attack, server-side norm bounding, or the §14
        edge hierarchy — whose edge pre-reduce needs the rows); False
        keeps the pre-§13 fused dispatch byte-for-byte."""
        return (self.attack_delta or self.norm_bound > 0.0
                or self.hierarchy.enabled)

    def stages(self) -> tuple:
        """The declared ``[local_train, attack, privacy, codec,
        aggregate]`` list as (name, enabled) pairs — what every engine
        assembles (tests assert the three engines agree)."""
        return (
            ("local_train", True),
            ("attack", self.adversary.enabled),
            ("privacy", self.privacy.enabled),
            ("codec", self.compression.enabled),
            ("aggregate", True),
        )

    def scope(self, *stages):
        """``jax.named_scope`` over the computation of ``stages``, named
        by those of them that are enabled, joined by ``+`` where one fused
        computation runs several (``privacy+codec+aggregate``), so a
        profile finds each stage's operations by name; a no-op when none
        of them is enabled."""
        on = dict(self.stages())
        name = "+".join(s for s in stages if on[s])
        return jax.named_scope(name) if name else contextlib.nullcontext()

    # -- attack stage ------------------------------------------------------
    def fold_key(self, round_key):
        """Round's Byzantine key (None when the adversary is off, so the
        benign trace never folds an extra key)."""
        if not self.adversary.enabled:
            return None
        return byz.fold_byz_key(round_key)

    def _mask(self, byz_key, rows: int):
        pop = self.num_clients if self.num_clients else rows
        return byz.attacker_mask(byz_key, pop,
                                 self.adversary.num_attackers)

    def attacked_flags(self, byz_key, gids=None, *, rows: int = 0):
        """(rows,) bool poison mask for the data-level attack, sliced to
        this engine's rows; None when no label flip is configured (the
        local_train signature stays 4-ary and traces unchanged)."""
        if not self.flip_data:
            return None
        mask = self._mask(byz_key, rows if gids is None else 0)
        if gids is None:
            return mask
        return mask[gids]

    def attack_rows(self, vecs, byz_key, gids=None, *, axes=None):
        """Stage 2 on a flat (rows, P) delta matrix. ``gids`` maps rows
        to global client ids (None: rows ARE the population). ``axes``:
        client mesh axes when the rows are a shard — ALIE's honest
        moments then psum across shards so colluding attackers agree."""
        if not self.attack_delta:
            return vecs
        with jax.named_scope("attack"):
            mask_full = self._mask(byz_key, vecs.shape[0])
            if gids is None:
                gids = jnp.arange(vecs.shape[0], dtype=jnp.int32)
                mask = mask_full
            else:
                mask = mask_full[gids]
            stats = None
            if axes is not None and self.adversary.kind == "alie":
                stats = byz.honest_stats_sharded(vecs, mask, axes)
            return byz.apply_attack(vecs, mask, self.adversary, byz_key,
                                    gids, stats=stats)

    # -- privacy + codec (per-row release, fault engines) ------------------
    def release_rows(self, vecs, keys, resid, *, byz_key=None, gids=None,
                     axes=None):
        """attack → privacy → codec on per-client rows, NO reduction:
        the fault-aware engines buffer/mask individual wire values, so
        a Byzantine row that also straggles is buffered CORRUPTED —
        exactly the §11 composition. Attack-off: verbatim
        ``cx.release_flat``."""
        vecs = self.attack_rows(vecs, byz_key, gids, axes=axes)
        with self.scope("privacy", "codec"):
            return cx.release_flat(vecs, keys, self.privacy,
                                   self.compression, resid)

    # -- aggregate stage helpers -------------------------------------------
    def _bound_rows(self, rel):
        """Server-side norm bounding (AggConfig.norm_bound): clip what
        the server RECEIVED, row by row, before any reduction. Static
        no-op at 0.0."""
        if self.norm_bound > 0.0:
            return byz.norm_clip_rows(rel, self.norm_bound)
        return rel

    def hier_reduce_flat(self, rel, weights):
        """Aggregate-stage reduce on materialized (rows, P) released
        rows: the flat ``agg.reduce_flat`` at E=1, the two-level
        client→edge→server reduce otherwise (DESIGN.md §14). Edge e owns
        the contiguous row block [e·C/E, (e+1)·C/E); each edge runs the
        configured rule over its OWN rows (the robust rules' trim depth
        shrinks with the C/E edge population — their ``reduce_flat``
        derives k from the input shape), then the linear family sums the
        edge partials (the same weighted moment, reassociated) while the
        robust family re-runs the rule over the E candidates weighted by
        edge mass."""
        E = self.hierarchy.num_edges
        if E <= 1:
            return self.agg.reduce_flat(rel, weights)
        c = rel.shape[0]
        v = rel.reshape(E, c // E, rel.shape[1])
        w = weights.astype(jnp.float32).reshape(E, c // E)
        if self.agg.linear:
            # linear reduce_flat is the weighted flat sum, so the edge
            # partials (computed against the globally-normalized
            # weights) just add up to the server update
            return jnp.sum(jnp.stack(
                [self.agg.reduce_flat(v[e], w[e]) for e in range(E)]),
                axis=0)
        # robust rules with a surviving-weight renormalization are
        # scale-invariant in the weights, but the k=0 trimmed-mean
        # degenerate case is a plain weighted sum that assumes its
        # weights total 1 — so each edge reduces against WITHIN-edge
        # normalized weights (a proper edge mean either way) and the
        # server rule weighs the candidates by edge mass
        mass = jnp.sum(w, axis=1)  # (E,)
        wn = w / jnp.maximum(mass, 1e-12)[:, None]
        edge_rows = jnp.stack(
            [self.agg.reduce_flat(v[e], wn[e]) for e in range(E)])
        return self.agg.reduce_flat(edge_rows, mass)

    def _two_hop_reduce(self, rel, weights, axes):
        """§14 robust reduce for the sharded engine on an ('edge', …)
        mesh: hop 1 all-gathers released rows WITHIN the edge
        (``axes[1:]``) and every edge pre-reduces its own C/E rows to one
        candidate (replicated in-edge); hop 2 all-gathers only the E
        candidate rows across the edge axis (``axes[0]``) — carrying the
        §10 int8 wire layout when the codec is on, with deterministic
        round-to-nearest (the candidate is an edge-level value with no
        per-client rounding key; it is identical on every in-edge
        device) — and the server rule runs replicated over (E, P). The
        dominant collective shrinks from O(C·P) cross-fleet to O(E·P)
        cross-edge."""
        agg, comp = self.agg, self.compression
        edge_ax, intra = axes[0], axes[1:]
        edge_vecs = jax.lax.all_gather(rel, intra, axis=0, tiled=True)
        edge_w = jax.lax.all_gather(weights, intra, axis=0, tiled=True)
        # within-edge normalized, as in hier_reduce_flat: the k=0
        # trimmed-mean degenerate case is a weights-sum-to-1 linear sum
        mass = jnp.sum(edge_w)
        cand = agg.reduce_flat(
            edge_vecs, edge_w / jnp.maximum(mass, 1e-12))[None, :]
        mass = mass[None]  # (1,)
        if comp.enabled and comp.kind == "int8":
            q, scales = cx.quantize_int8(cand, uniform=None)
            all_q = jax.lax.all_gather(q, edge_ax, axis=0, tiled=True)
            all_s = jax.lax.all_gather(scales, edge_ax, axis=0,
                                       tiled=True)
            all_cand = cx.dequantize_int8(all_q, all_s)
        else:
            all_cand = jax.lax.all_gather(cand, edge_ax, axis=0,
                                          tiled=True)
        all_mass = jax.lax.all_gather(mass, edge_ax, axis=0, tiled=True)
        return agg.reduce_flat(all_cand, all_mass)

    # -- full stacked tail: [attack →] privacy → codec → aggregate ---------
    def reduce_apply(self, server_state, global_params, deltas, weights,
                     keys, *, losses, idx, resid, byz_key=None):
        """Round tail for client-stacked engines (the vmapped GPO round
        and the backbone/LoRA trainers): takes the raw local-train delta
        trees, returns (new_global, new_server_state, new_resid).
        ``idx`` are the participants' global ids (None = full
        participation); ``resid`` is the participants' EF residual slice
        (None without error feedback)."""
        agg, priv, comp = self.agg, self.privacy, self.compression
        if not self.restructured:
            # pre-§13 dispatch, byte-for-byte (the §9/§10 pins ride it)
            with self.scope("privacy", "codec", "aggregate"):
                if comp.enabled:
                    w_eff = agg.weigh(server_state, weights, idx)
                    delta_vec, new_r = cx.transport_delta_flat(
                        tree_ravel_clients(deltas), w_eff, keys, priv, comp,
                        agg, resid, use_pallas=self.use_pallas)
                    delta = tree_unflatten_from_vector(delta_vec,
                                                       global_params)
                    new_global, server_state = agg.apply(
                        server_state, global_params, delta, losses=losses,
                        idx=idx)
                    return new_global, server_state, new_r
                if priv.enabled:
                    w_eff = agg.weigh(server_state, weights, idx)
                    delta_vec = dp.private_delta_flat(
                        tree_ravel_clients(deltas), w_eff, keys, priv, agg,
                        use_pallas=self.use_pallas)
                    delta = tree_unflatten_from_vector(delta_vec,
                                                       global_params)
                    new_global, server_state = agg.apply(
                        server_state, global_params, delta, losses=losses,
                        idx=idx)
                    return new_global, server_state, resid
                new_global, server_state = agg.step(
                    server_state, global_params, deltas, weights,
                    losses=losses, idx=idx)
                return new_global, server_state, resid
        # restructured: materialize attacked/released rows, bound, reduce
        with self.scope("aggregate"):
            w_eff = agg.weigh(server_state, weights, idx)
        vecs = self.attack_rows(tree_ravel_clients(deltas), byz_key, idx)
        with self.scope("privacy", "codec"):
            rel, new_r = cx.release_flat(vecs, keys, priv, comp, resid)
        with self.scope("aggregate"):
            rel = self._bound_rows(rel)
            delta = tree_unflatten_from_vector(
                self.hier_reduce_flat(rel, w_eff), global_params)
            new_global, server_state = agg.apply(
                server_state, global_params, delta, losses=losses, idx=idx)
        return new_global, server_state, new_r

    # -- sharded middle: [attack →] privacy → codec → reduce collective ----
    def sharded_delta(self, deltas, weights, keys, global_prev, resid,
                      axes, *, byz_key=None, gids=None):
        """Round middle for the shard_map engine: local (C_local, …)
        delta trees in, (reduced delta tree, new shard-local residual)
        out. Linear family ends in ONE weighted psum; robust family
        all-gathers rows. Attack-off + norm_bound 0: verbatim pre-§13
        branches (collective schedule byte-identical — dryrun/hlo_cost
        verified)."""
        agg, priv, comp = self.agg, self.privacy, self.compression
        ef = comp.enabled and comp.error_feedback
        if not self.restructured:
            with self.scope("privacy", "codec", "aggregate"):
                new_resid = None
                if comp.enabled:
                    vecs = tree_ravel_clients(deltas)
                    if agg.linear:
                        local_vec, new_resid = cx.transport_delta_flat(
                            vecs, weights, keys, priv, comp, agg, resid,
                            use_pallas=self.use_pallas)
                        delta = tree_unflatten_from_vector(
                            jax.lax.psum(local_vec, axes), global_prev)
                    else:
                        x = (dp.privatize_flat(vecs, keys, priv)
                             if priv.enabled else vecs.astype(jnp.float32))
                        u = x + resid if ef else x
                        if comp.kind == "int8":
                            uniform = (cx.client_uniform(keys, u.shape)
                                       if comp.stochastic else None)
                            q, scales = cx.quantize_int8(u, uniform=uniform)
                            t_local = cx.dequantize_int8(q, scales)
                            all_q = jax.lax.all_gather(q, axes, axis=0,
                                                       tiled=True)
                            all_s = jax.lax.all_gather(scales, axes, axis=0,
                                                       tiled=True)
                            all_vecs = cx.dequantize_int8(all_q, all_s)
                        else:  # topk: dense f32 layout of the sparse shard
                            t_local, _ = cx.sparsify_topk(u, comp.topk_frac)
                            all_vecs = jax.lax.all_gather(t_local, axes,
                                                          axis=0, tiled=True)
                        new_resid = u - t_local if ef else None
                        all_w = jax.lax.all_gather(weights, axes, axis=0,
                                                   tiled=True)
                        delta = tree_unflatten_from_vector(
                            agg.reduce_flat(all_vecs, all_w), global_prev)
                elif priv.enabled:
                    vecs = tree_ravel_clients(deltas)
                    if agg.linear:
                        local_vec = dp.clip_noise_reduce(
                            vecs, weights, keys, priv,
                            use_pallas=self.use_pallas)
                        delta = tree_unflatten_from_vector(
                            jax.lax.psum(local_vec, axes), global_prev)
                    else:
                        pvecs = dp.privatize_flat(vecs, keys, priv)
                        all_vecs = jax.lax.all_gather(pvecs, axes, axis=0,
                                                      tiled=True)
                        all_w = jax.lax.all_gather(weights, axes, axis=0,
                                                   tiled=True)
                        delta = tree_unflatten_from_vector(
                            agg.reduce_flat(all_vecs, all_w), global_prev)
                elif agg.linear:
                    if self.use_pallas:
                        vecs = tree_ravel_clients(deltas)
                        local_vec = fedavg_reduce(
                            vecs, weights.astype(jnp.float32))
                        delta = tree_unflatten_from_vector(
                            jax.lax.psum(local_vec, axes), global_prev)
                    else:
                        local_weighted = jax.tree.map(
                            lambda x: jnp.sum(
                                x.astype(jnp.float32)
                                * weights.reshape(
                                    (-1,) + (1,) * (x.ndim - 1)),
                                axis=0),
                            deltas)
                        delta = fedavg_allreduce(
                            local_weighted, jnp.asarray(1.0, jnp.float32),
                            axes)
                else:
                    vecs = tree_ravel_clients(deltas)
                    all_vecs = jax.lax.all_gather(vecs, axes, axis=0,
                                                  tiled=True)
                    all_w = jax.lax.all_gather(weights, axes, axis=0,
                                               tiled=True)
                    delta = tree_unflatten_from_vector(
                        agg.reduce_flat(all_vecs, all_w), global_prev)
            return delta, new_resid
        # restructured: attack + release stay shard-local (the corrupt
        # rows cross the wire like honest ones); the norm bound clips
        # rows BEFORE the reduce, so the linear family keeps its ONE
        # (P,) f32 psum — byte-identical collective schedule even with
        # the defense engaged (the robust family gathers f32 rows,
        # forgoing the int8 wire layout under an active attack).
        vecs = self.attack_rows(tree_ravel_clients(deltas), byz_key,
                                gids, axes=axes)
        with self.scope("privacy", "codec"):
            rel, new_resid = cx.release_flat(vecs, keys, priv, comp, resid)
        with self.scope("aggregate"):
            rel = self._bound_rows(rel)
            if agg.linear:
                # ONE weighted psum over ALL client axes — on an ('edge',
                # 'data') mesh this IS the composed two-hop partial-sum
                # schedule (§14: the linear family's bytes are unchanged
                # by the hierarchy)
                delta_vec = jax.lax.psum(agg.reduce_flat(rel, weights),
                                         axes)
            elif self.hierarchy.enabled and len(axes) > 1:
                delta_vec = self._two_hop_reduce(rel, weights, axes)
            else:
                all_vecs = jax.lax.all_gather(rel, axes, axis=0,
                                              tiled=True)
                all_w = jax.lax.all_gather(weights, axes, axis=0,
                                           tiled=True)
                delta_vec = agg.reduce_flat(all_vecs, all_w)
        return (tree_unflatten_from_vector(delta_vec, global_prev),
                new_resid if ef else None)

    # -- aggregate under fault masking (§11 ∘ §13) -------------------------
    def masked_reduce(self, contrib, w_c, mask_c, *, trim_frac):
        """Degraded-mode reduce on the FULL (C, P) blended contribution
        matrix (fresh + buffered rows): linear renormalizes over
        survivors; median/trimmed_mean shrink their trim depth with the
        survivor count; the §13 defenses are mask-tolerant through their
        weights (weight-0 rows are excluded from selection). The norm
        bound clips the blended rows — what the server is about to
        absorb — first."""
        agg = self.agg
        with self.scope("aggregate"):
            contrib = self._bound_rows(contrib)
            if agg.linear:
                wn = av.masked_mean_weights(w_c, mask_c)
                return agg.reduce_flat(contrib, wn)
            if agg.name in ("median", "trimmed_mean"):
                return av.masked_robust_reduce_flat(
                    contrib, w_c, mask_c, name=agg.name, trim_frac=trim_frac)
            return agg.reduce_flat(contrib, jnp.where(mask_c, w_c, 0.0))

    def masked_reduce_sharded(self, contrib_l, w_c, mask_c, gids, axes, *,
                              trim_frac):
        """``masked_reduce`` for the sharded fault round: linear keeps
        the shard-local partial sum + ONE psum; robust/defense families
        all-gather the blended rows and reduce replicated."""
        agg = self.agg
        with self.scope("aggregate"):
            contrib_l = self._bound_rows(contrib_l)
            if agg.linear:
                wn_l = av.masked_mean_weights(w_c, mask_c)[gids]
                if self.use_pallas:
                    local_vec = fedavg_reduce(contrib_l, wn_l)
                else:
                    local_vec = jnp.einsum("c,cp->p", wn_l, contrib_l)
                return jax.lax.psum(local_vec, axes)
            all_vecs = jax.lax.all_gather(contrib_l, axes, axis=0,
                                          tiled=True)
            if agg.name in ("median", "trimmed_mean"):
                return av.masked_robust_reduce_flat(
                    all_vecs, w_c, mask_c, name=agg.name,
                    trim_frac=trim_frac)
            return agg.reduce_flat(all_vecs, jnp.where(mask_c, w_c, 0.0))


def make_pipeline(fed_cfg, *, agg: ServerAggregator,
                  num_clients: Optional[int] = None) -> RoundPipeline:
    """Assemble the round pipeline from a FedConfig + built aggregator
    (the one call every engine makes)."""
    return RoundPipeline(
        adversary=fed_cfg.adversary, privacy=fed_cfg.privacy,
        compression=fed_cfg.compression, agg=agg,
        num_clients=num_clients,
        use_pallas=fed_cfg.use_pallas_aggregation,
        hierarchy=fed_cfg.hierarchy)
