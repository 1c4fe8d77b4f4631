"""PluralLLM federated runtime (paper §3, §4.3).

Round structure (faithful to the paper):
  1. server broadcasts global GPO params to all training clients (groups);
  2. every client runs ``local_epochs`` Adam steps; each step samples
     context questions + target questions from the client's private
     preference data (in-context objective, Eq. 1; with
     ``AggConfig.prox_mu > 0`` a FedProx proximal term anchors the local
     model to the round's broadcast global);
  3. clients transmit parameter *deltas*; with ``FedConfig.privacy``
     enabled each flat delta is L2-clipped and Gaussian-noised BEFORE it
     leaves the client (DESIGN.md §9, ``core/privacy.py`` — the Rényi
     accountant folds the per-round ε into ``History.round_eps``); with
     ``FedConfig.compression`` enabled the released delta is then int8-
     quantized or top-k-sparsified with an EF21 error-feedback residual
     (DESIGN.md §10, ``core/compression.py``); the
     server reduces the (privatized) deltas and applies the configured
     ``ServerAggregator`` update (DESIGN.md §7 — the paper's Eq. 2-3
     FedAvg is the default strategy) and redistributes.

Two execution engines expose the same round semantics:

* ``FederatedGPO`` — clients vmapped on one device; the engine the
  examples, the launchers and the chip benchmark's training cell run.
* ``make_sharded_round`` — clients laid out on the mesh `data` axis via
  ``shard_map``; local epochs run without any cross-client collective and
  the round ends in ONE weighted psum (+ the hierarchical `pod` axis on
  multi-pod meshes). This is the multi-chip engine the dry-run lowers.

``FederatedGPO`` writes its round once (DESIGN.md §3): one participant
head (cohort draw, broadcast, vmapped local epochs) and one of two tails,
chosen statically — the pipeline's fused reduce, or with
``AvailabilityConfig`` enabled the fault-aware release, blend and masked
reduce. Two drivers run that round:

* ``run(engine="scan")`` (default) — the requested rounds are ONE jitted
  ``lax.scan`` (or blocks of ``log_every`` rounds when live logging is
  requested). Per-round losses and the eval-cadence alignment scores
  accumulate on device and transfer to host once per block; the
  per-client optimizer buffers are donated into the call. Zero per-round
  Python dispatch or device→host sync.
* ``run(engine="loop")`` — one jitted round per call with a host sync on
  the loss. The scan driver runs a block's tail shorter than a chunk this
  way, and the equivalence tests hold the scan against it.

Both drivers derive per-round RNG keys identically, so they produce the
same ``History`` up to float reassociation. The round-key chain is the
trainer's: it starts at ``PRNGKey(seed + 1)`` and every ``run`` call
continues it, so ``run(10)`` twice trains on the same draws as
``run(20)``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FedConfig, GPOConfig
from repro.core import adversary as byz
from repro.core import availability as av
from repro.core import fairness, privacy as dp
from repro.core.aggregation import ServerAggregator, make_aggregator
from repro.core.pipeline import make_pipeline
from repro.core.fedavg import broadcast_to_clients, normalize_weights
from repro.core.gpo import gpo_loss, init_gpo_params, predict_preferences
from repro.data.surveys import SurveyData, sample_icl_batch
from repro.optim import adam
from repro.utils.pytree import (
    tree_count_params,
    tree_index,
    tree_ravel_clients,
    tree_sq_norm,
    tree_sub,
    tree_unflatten_from_vector,
)
from repro.utils.spans import span

PyTree = Any


# ---------------------------------------------------------------------------
# Local training (one client, `local_epochs` steps) — shared by both engines
# ---------------------------------------------------------------------------
def _make_local_train(gpo_cfg: GPOConfig, fed_cfg: FedConfig,
                      data: SurveyData, opt):
    """Local client objective. With ``AggConfig.prox_mu > 0`` the FedProx
    proximal term (mu/2)*||theta - theta_global||^2 anchors each local
    step to the round's broadcast global (= the entry params); the
    reported loss stays the task loss so strategies compare on Eq. 1.
    The mu == 0 path traces byte-identical to the seed objective.

    With a data-level adversary configured (``kind="label_flip"``,
    DESIGN.md §13) the returned function gains a trailing per-client
    ``attacked`` flag and poisons the attacked clients' sampled
    preference rows — context AND target, the Byzantine client poisons
    everything it feeds the optimizer — via ``byz.flip_preferences``.
    The attack-off signature and trace are unchanged (static branch)."""
    mu = fed_cfg.agg.prox_mu
    flip = fed_cfg.adversary.enabled and fed_cfg.adversary.data_level

    def local_body(params, opt_state, key, group_id, attacked):
        anchor = params  # the round's broadcast global model

        def epoch_step(carry, k):
            params, opt_state = carry
            batch = sample_icl_batch(k, data, group_id,
                                     fed_cfg.num_context, fed_cfg.num_target)
            if flip:
                def poison(y):
                    y = y.astype(jnp.float32)
                    return jnp.where(
                        attacked,
                        byz.flip_preferences(y, data.num_options), y)

                batch = batch._replace(ctx_y=poison(batch.ctx_y),
                                       tgt_y=poison(batch.tgt_y))
            if mu > 0.0:
                def objective(p):
                    task = gpo_loss(p, gpo_cfg, batch.ctx_x, batch.ctx_y,
                                    batch.tgt_x, batch.tgt_y)
                    prox = 0.5 * mu * tree_sq_norm(tree_sub(p, anchor))
                    return task + prox, task

                (_, loss), grads = jax.value_and_grad(
                    objective, has_aux=True)(params)
            else:
                loss, grads = jax.value_and_grad(gpo_loss)(
                    params, gpo_cfg, batch.ctx_x, batch.ctx_y, batch.tgt_x,
                    batch.tgt_y)
            params, opt_state = opt.update(grads, opt_state, params)
            return (params, opt_state), loss

        keys = jax.random.split(key, fed_cfg.local_epochs)
        (params, opt_state), losses = jax.lax.scan(
            epoch_step, (params, opt_state), keys)
        return params, opt_state, jnp.mean(losses)

    if flip:
        def local_train(params, opt_state, key, group_id, attacked):
            return local_body(params, opt_state, key, group_id, attacked)
    else:
        def local_train(params, opt_state, key, group_id):
            return local_body(params, opt_state, key, group_id, None)

    return local_train


def _make_eval_group(gpo_cfg: GPOConfig, fed_cfg: FedConfig, data: SurveyData):
    """AS of the global model on one (unseen) group — Eq. 4."""

    def eval_group(params, key, group_id):
        batch = sample_icl_batch(key, data, group_id,
                                 fed_cfg.num_context, fed_cfg.num_target)
        pred = predict_preferences(params, gpo_cfg, batch.ctx_x, batch.ctx_y,
                                   batch.tgt_x, data.num_options)
        truth = batch.tgt_y.reshape(-1, data.num_options)
        return fairness.alignment_score(pred, truth)

    return eval_group


# ---------------------------------------------------------------------------
# Engine 1: vmapped clients on one device
# ---------------------------------------------------------------------------
def _deleted(tree) -> bool:
    """Whether any buffer of ``tree`` was consumed (donated); False for
    None."""
    return any(getattr(x, "is_deleted", lambda: False)()
               for x in jax.tree.leaves(tree))


@dataclass
class History:
    round_loss: list = field(default_factory=list)  # mean client loss / round
    eval_rounds: list = field(default_factory=list)
    eval_scores: list = field(default_factory=list)  # (K,) per eval round
    eval_mean_as: list = field(default_factory=list)
    eval_fi: list = field(default_factory=list)
    eval_cov: list = field(default_factory=list)
    # DP accounting (DESIGN.md §9): cumulative ε at PrivacyConfig.
    # target_delta AFTER each round, counted across every `run` call on
    # the trainer. Empty when the privacy pipeline is disabled; inf per
    # round for clip-only runs (clipping alone carries no DP guarantee).
    round_eps: list = field(default_factory=list)
    # fault injection (DESIGN.md §11): per-round count of updates the
    # server actually absorbed (fresh releases + buffered arrivals).
    # Empty when AvailabilityConfig is disabled.
    round_survivors: list = field(default_factory=list)


class FederatedGPO:
    def __init__(self, gpo_cfg: GPOConfig, fed_cfg: FedConfig,
                 data: SurveyData, train_groups: np.ndarray,
                 eval_groups: np.ndarray):
        gpo_cfg = fed_cfg.resolve_gpo(gpo_cfg)  # runtime attention override
        assert gpo_cfg.d_embed == data.phi.shape[-1]
        fed_cfg.privacy.validate()
        fed_cfg.compression.validate()
        fed_cfg.avail.validate()
        fed_cfg.adversary.validate()
        # §14 edge topology: validated against the per-round participant
        # count (edges partition the PARTICIPANTS, contiguous + equal
        # size). The fault-aware round bypasses the hierarchy — buffered
        # arrivals break the static edge assignment — so the two stay
        # mutually exclusive rather than silently degrading.
        m_part = min(fed_cfg.batch_groups or len(train_groups),
                     len(train_groups))
        fed_cfg.hierarchy.validate(m_part)
        if fed_cfg.hierarchy.enabled and fed_cfg.avail.enabled:
            raise ValueError(
                "hierarchy.num_edges > 1 does not compose with the §11 "
                "fault simulator: the buffered/masked reduce aggregates "
                "flat (edge assignment is static per round)")
        dp.check_adaptive_privacy(fed_cfg)
        byz.check_defense_composition(fed_cfg)
        self.gpo_cfg, self.fed_cfg, self.data = gpo_cfg, fed_cfg, data
        self.train_groups = jnp.asarray(train_groups, jnp.int32)
        self.eval_groups = jnp.asarray(eval_groups, jnp.int32)
        self.weights = normalize_weights(data.sizes[self.train_groups])
        self.opt = adam(fed_cfg.lr)
        self.agg = make_aggregator(
            fed_cfg.agg, num_clients=len(train_groups),
            use_pallas=fed_cfg.use_pallas_aggregation)

        key = jax.random.PRNGKey(fed_cfg.seed)
        self.global_params = init_gpo_params(gpo_cfg, key)
        # the round-key chain, carried across ``run`` calls
        self.round_key = jax.random.PRNGKey(fed_cfg.seed + 1)
        self.server_state = self.agg.init(self.global_params)
        # EF21-style compression residual (DESIGN.md §10): one flat f32
        # row per client, carried across rounds next to the server state
        # (None keeps the pre-compression trace byte-identical).
        comp = fed_cfg.compression
        if comp.enabled and comp.error_feedback:
            self.ef_resid = jnp.zeros(
                (len(train_groups), tree_count_params(self.global_params)),
                jnp.float32)
        else:
            self.ef_resid = None
        # fault injection (DESIGN.md §11): availability/failure state —
        # crash-rejoin traces plus the straggler in-flight buffer — rides
        # next to the server state; None keeps the fault-free trace
        # byte-identical (a None leaf adds no inputs to the round).
        if fed_cfg.avail.enabled:
            self.fault_state = av.init_fault_state(
                len(train_groups), tree_count_params(self.global_params))
        else:
            self.fault_state = None
        per_client = broadcast_to_clients(self.global_params,
                                          len(train_groups))
        self.opt_states = jax.vmap(self.opt.init)(per_client)

        local_train = _make_local_train(gpo_cfg, fed_cfg, data, self.opt)
        eval_group = _make_eval_group(gpo_cfg, fed_cfg, data)
        num_clients = len(train_groups)
        # partial participation (beyond-paper ablation): sample
        # batch_groups clients per round; weights renormalize over the
        # participants (paper §4.3 assumes full participation).
        m = fed_cfg.batch_groups or num_clients
        m = min(m, num_clients)

        # DP accounting (DESIGN.md §9, §11): one sampled Gaussian
        # mechanism per round at the REALIZED participation rate
        # q = (m/C) · release_rate — a client releases a delta only when
        # it is sampled AND online AND does not crash, so the effective
        # per-round inclusion probability shrinks under faults (the
        # availability draws are independent of the data, making this the
        # standard amplification-by-subsampling composition; stragglers
        # still release — late — and are counted). release_rate is 1.0
        # with faults disabled, keeping the pre-§11 epsilon exactly.
        self._accountant = dp.make_accountant(
            fed_cfg.privacy,
            (m / num_clients) * fed_cfg.avail.release_rate())
        self._rounds_elapsed = 0

        agg = self.agg
        ef = comp.enabled and comp.error_feedback
        # round-stage pipeline (DESIGN.md §13): the [local_train, attack,
        # privacy, codec, aggregate] sequence assembles ONCE here; both
        # round tails below delegate the stage dispatch to it (the
        # attack-off pipeline traces the exact pre-§13 computation).
        pipe = make_pipeline(fed_cfg, agg=agg, num_clients=num_clients)
        avail = fed_cfg.avail

        def train_cohort(global_params, opt_states, key):
            """The round's head, shared by both tails: draw the cohort,
            broadcast the global model and run every participant's local
            epochs."""
            k_sub, k_train = jax.random.split(key)
            if m < num_clients:
                idx = jax.random.choice(k_sub, num_clients, (m,),
                                        replace=False)
            else:
                idx = jnp.arange(num_clients)
            groups = self.train_groups[idx]
            sizes = data.sizes[groups].astype(jnp.float32)
            w = sizes / jnp.sum(sizes)
            client_params = broadcast_to_clients(global_params, m)
            opt_sub = jax.tree.map(lambda x: x[idx], opt_states)
            keys = jax.random.split(k_train, m)
            # the Byzantine key folds out of the ROUND key (like the §11
            # fault key, its own tag) — None when the adversary is off,
            # so the benign trace never folds it
            bk = pipe.fold_key(key)
            train_args = (client_params, opt_sub, keys, groups)
            if pipe.flip_data:
                train_args += (pipe.attacked_flags(bk, idx),)
            with jax.named_scope("local_train"):
                new_client_params, opt_sub, losses = jax.vmap(local_train)(
                    *train_args)
            return (idx, w, keys, bk, client_params, new_client_params,
                    opt_sub, losses)

        def sync_round(state, key):
            """Fault-free tail: every participant's delta goes through the
            pipeline's fused [attack →] privacy → codec → aggregate."""
            global_params, opt_states, resid, fault, server_state = state
            (idx, w, keys, bk, client_params, new_client_params, opt_sub,
             losses) = train_cohort(global_params, opt_states, key)
            opt_states = jax.tree.map(
                lambda full, sub: full.at[idx].set(sub), opt_states,
                opt_sub)
            # delta contract (DESIGN.md §7): clients ship theta_g - theta;
            # the server runs the pipeline's [attack →] privacy → codec →
            # aggregate tail (Eq. 3 FedAvg being the default strategy;
            # the EF residual rows of this round's participants update in
            # place, non-sampled clients keep theirs).
            deltas = tree_sub(new_client_params, client_params)
            new_global, server_state, new_r = pipe.reduce_apply(
                server_state, global_params, deltas, w, keys,
                losses=losses, idx=idx,
                resid=resid[idx] if ef else None, byz_key=bk)
            if ef:
                resid = resid.at[idx].set(new_r)
            return ((new_global, opt_states, resid, fault, server_state),
                    losses, None, None)

        # Fault-aware tail (DESIGN.md §11). The fault round trades the
        # fused reduce kernels for a per-client release (payloads must be
        # individually maskable/bufferable) and keeps every failure
        # decision inside the trace as masks: no Python branching on
        # schedule values.
        def fault_round(state, key):
            global_params, opt_states, resid, fault, server_state = state
            (idx, w, keys, bk, client_params, new_client_params, opt_sub,
             losses) = train_cohort(global_params, opt_states, key)
            w_eff = agg.weigh(server_state, w, idx)
            # the failure schedule: a pure function of (round key, client
            # index, carried fault state) — replicated-computable, so the
            # sharded engine replays it bit-identically (fold_fault_key).
            fault_key = av.fold_fault_key(key)
            sched = av.round_schedule(fault_key, fault, avail, num_clients)
            # sampling is oblivious to availability (the coordinator
            # cannot know who will fail); realized participation is
            # sampled ∧ available. Draws of non-sampled clients are
            # discarded — only their in-flight arrivals act this round.
            sampled = jnp.zeros((num_clients,), bool).at[idx].set(True)
            sched = sched._replace(
                available=sched.available & sampled,
                fresh=sched.fresh & sampled,
                crashed=sched.crashed & sampled,
                straggle=sched.straggle & sampled)
            # opt states advance only where the round's local work
            # survived: offline clients never trained, crashed clients
            # lost theirs with the crash
            keep = (sched.fresh | sched.straggle)[idx]

            def merge(full, sub):
                k_ = keep.reshape((-1,) + (1,) * (sub.ndim - 1))
                return full.at[idx].set(jnp.where(k_, sub, full[idx]))

            opt_states = jax.tree.map(merge, opt_states, opt_sub)
            # per-client release (pipeline stages 2-4: attack, DP, then
            # EF/codec — NO reduction): a Byzantine row that straggles is
            # buffered CORRUPTED, the §11 ∘ §13 composition. The EF21
            # residual rows advance exactly for releasing clients
            # (fresh + stragglers — they do transmit, just late);
            # crashed/offline rows are untouched (delta never released).
            deltas = tree_sub(new_client_params, client_params)
            r_sub = resid[idx] if ef else None
            rel_sub, new_r = pipe.release_rows(
                tree_ravel_clients(deltas), keys, r_sub, byz_key=bk,
                gids=idx)
            if ef:
                resid = resid.at[idx].set(
                    jnp.where(keep[:, None], new_r, resid[idx]))
            rel_full = jnp.zeros(
                (num_clients, rel_sub.shape[1]),
                jnp.float32).at[idx].set(rel_sub)
            w_full = jnp.zeros((num_clients,), jnp.float32).at[idx].set(
                w_eff.astype(jnp.float32))
            # this round's contributions: fresh releases at full weight +
            # buffered arrivals discounted by realized staleness. A
            # client that is both (its stale upload lands while it also
            # trains fresh) contributes the weight-averaged row.
            disc = av.staleness_discount(sched.staleness,
                                         fed_cfg.agg.staleness_power)
            w_fresh = jnp.where(sched.fresh, w_full, 0.0)
            w_arr = jnp.where(sched.arrive,
                              fault.pending_weight * disc, 0.0)
            w_c = w_fresh + w_arr
            mask_c = w_c > 0.0
            contrib = jnp.where(
                mask_c[:, None],
                (w_fresh[:, None] * rel_full
                 + w_arr[:, None] * fault.pending)
                / jnp.maximum(w_c, 1e-12)[:, None], 0.0)
            n_released = (jnp.sum(sched.fresh.astype(jnp.int32))
                          + jnp.sum(sched.arrive.astype(jnp.int32)))
            any_surv = n_released > 0
            # degraded-mode reduce (pipeline stage 5 under fault masking):
            # linear renormalizes over survivors; robust shrinks its trim
            # depth with the survivor count; defenses drop weight-0 rows
            delta_vec = pipe.masked_reduce(
                contrib, w_c, mask_c, trim_frac=fed_cfg.agg.trim_frac)
            delta = tree_unflatten_from_vector(delta_vec, global_params)
            kw = {}
            if agg.buffered:
                kw = dict(mass=jnp.sum(w_c),
                          released=n_released.astype(jnp.float32))
            if agg.needs_losses:
                # adaptive: the server only observed losses that arrived
                # with a fresh release
                kw["mask"] = sched.fresh[idx]
            with pipe.scope("aggregate"):
                new_global, new_state = agg.apply(
                    server_state, global_params, delta, losses=losses,
                    idx=idx, **kw)
            # zero-survivor round: verified no-op on params AND AggState
            new_global = av.tree_where(any_surv, new_global, global_params)
            server_state = av.tree_where(any_surv, new_state, server_state)
            fault = av.advance_fault_state(fault, sched, rel_full, w_full,
                                           avail.rejoin_rounds)
            return ((new_global, opt_states, resid, fault, server_state),
                    losses, keep, n_released)

        # a STATIC Python branch: with AvailabilityConfig disabled (the
        # default) the round traces exactly the fault-free computation —
        # the bit-equal pin in tests/test_availability.py rides on this
        round_step = fault_round if avail.enabled else sync_round

        def mean_loss(losses, keep):
            """The round's mean client loss; under faults, over the
            clients whose local round survived (``keep``)."""
            if keep is None:
                return jnp.mean(losses)
            n_train = jnp.sum(keep.astype(jnp.float32))
            return (jnp.sum(jnp.where(keep, losses, 0.0))
                    / jnp.maximum(n_train, 1.0))

        def eval_fn(global_params, key):
            keys = jax.random.split(key, len(eval_groups))
            with jax.named_scope("eval"):
                return jax.vmap(eval_group, in_axes=(None, 0, 0))(
                    global_params, keys, self.eval_groups)

        num_eval = len(eval_groups)

        # The carried round state is (global_params, opt_states, resid,
        # fault, server_state); ``fault`` is None with faults off and
        # ``resid`` without error feedback, and a None leaf adds no
        # inputs to the trace. A whole block of rounds is one jitted
        # lax.scan. ``eval_mask`` (bool per round, known on the host)
        # picks the rounds that also run the Eq. 4 evaluation; skipped
        # rounds emit zeros that the host discards, so metric
        # accumulation stays on device and the block performs exactly one
        # host transfer. Only the per-client optimizer buffers, the EF
        # compression residual and the fault state are donated: callers
        # (and the seed tests) legitimately hold references to the
        # previous global model across ``run`` calls. The server-
        # aggregator state rides in the scan carry so stateful strategies
        # fuse exactly like stateless FedAvg.
        @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
        def block_fn(global_params, opt_states, resid, fault, server_state,
                     key, eval_mask):
            def body(carry, do_eval):
                state, k = carry
                k, k_round, k_eval = jax.random.split(k, 3)
                state, losses, keep, n_rel = round_step(state, k_round)
                scores = jax.lax.cond(
                    do_eval,
                    lambda gp, ke: eval_fn(gp, ke).astype(jnp.float32),
                    lambda gp, ke: jnp.zeros((num_eval,), jnp.float32),
                    state[0], k_eval)
                return (state, k), (mean_loss(losses, keep), scores, n_rel)

            (state, key), (losses, scores, n_rel) = jax.lax.scan(
                body, ((global_params, opt_states, resid, fault,
                        server_state), key), eval_mask)
            return (*state, key, losses, scores, n_rel)

        # one round per call: the tail of a block shorter than a chunk,
        # the loop driver, and the reference for the equivalence tests
        @jax.jit
        def round_fn(global_params, opt_states, resid, fault, server_state,
                     key):
            state, losses, keep, n_rel = round_step(
                (global_params, opt_states, resid, fault, server_state),
                key)
            return (*state, mean_loss(losses, keep), n_rel)

        self._block = block_fn
        self._round = round_fn
        self._eval = jax.jit(eval_fn)

    def _eval_mask(self, rounds: int) -> np.ndarray:
        """Rounds that evaluate: every ``eval_every``-th and the last."""
        mask = np.zeros(rounds, np.bool_)
        mask[:: self.fed_cfg.eval_every] = True
        mask[rounds - 1] = True
        return mask

    def _note_privacy(self, hist: History, n: int) -> None:
        """Record cumulative ε after each of ``n`` newly-finished rounds
        (host-side; the accountant composes RDP linearly per round)."""
        self._rounds_elapsed += n
        if not self.fed_cfg.privacy.enabled:
            return
        for r in range(self._rounds_elapsed - n + 1,
                       self._rounds_elapsed + 1):
            hist.round_eps.append(
                self._accountant.epsilon(r) if self._accountant
                else float("inf"))

    def _append_eval(self, hist: History, r: int, scores: np.ndarray,
                     log_every: int) -> None:
        hist.eval_rounds.append(r)
        hist.eval_scores.append(scores)
        hist.eval_mean_as.append(float(scores.mean()))
        hist.eval_fi.append(float(fairness.fairness_index(scores)))
        hist.eval_cov.append(
            float(fairness.coefficient_of_variation(scores)))
        if log_every and r % log_every == 0:
            print(f"[fed] round {r:5d} loss={hist.round_loss[r]:.4f} "
                  f"AS={hist.eval_mean_as[-1]:.4f} "
                  f"FI={hist.eval_fi[-1]:.4f}")

    def run(self, rounds: int | None = None, log_every: int = 0,
            engine: str = "scan") -> History:
        """Run ``rounds`` FedAvg rounds and return the metric ``History``.

        ``engine="scan"`` executes the rounds as fused jitted scans;
        ``"loop"`` dispatches one jitted round at a time.
        """
        rounds = rounds or self.fed_cfg.rounds
        if rounds <= 0:
            return History()
        if engine == "scan":
            return self._run_scan(rounds, log_every)
        if engine == "loop":
            return self._run_loop(rounds, log_every)
        raise ValueError(f"unknown engine {engine!r} (want 'scan'|'loop')")

    def _run_scan(self, rounds: int, log_every: int) -> History:
        """The fused driver. Host spans (``utils/spans.py``): ``fed.run``
        around the call, ``fed.block`` per block with its ``fed.dispatch``
        (the mask's transfer and the block's enqueue), ``fed.fetch``
        (losses and scores to the host, which waits on the device) and
        ``fed.record`` (``History``, privacy accounting, eval metrics,
        log line); ``fed.round`` per round of a sub-block tail."""
        eval_mask = self._eval_mask(rounds)
        hist = History()
        # one fused block normally; with log_every, blocks of log_every
        # rounds so progress still reaches the console while training
        # (the RNG chain threads through the carried key, so chunking
        # does not change any per-round key).
        chunk = min(log_every, rounds) if log_every else rounds
        full_end = (rounds // chunk) * chunk
        with span("fed.run", rounds=rounds):
            for start in range(0, full_end, chunk):
                mask = eval_mask[start:start + chunk]
                with span("fed.block", rounds=len(mask)):
                    self._run_block(hist, mask, log_every)
            # remainder shorter than a chunk: run per-round (same key
            # chain) rather than compiling the fused block a second time
            # for a tail
            for r in range(full_end, rounds):
                self._dispatch_round(hist, r, eval_mask, log_every)
        return hist

    def _run_block(self, hist: History, mask: np.ndarray,
                   log_every: int) -> None:
        """One fused block of ``len(mask)`` rounds, appended to ``hist``."""
        with span("fed.dispatch"):
            try:
                (self.global_params, self.opt_states, self.ef_resid,
                 self.fault_state, self.server_state, self.round_key,
                 losses, scores, n_rel) = self._block(
                    self.global_params, self.opt_states, self.ef_resid,
                    self.fault_state, self.server_state, self.round_key,
                    jnp.asarray(mask))
            except BaseException:
                self._recover_donated_opt_states()
                raise
        with span("fed.fetch"):
            losses = np.asarray(losses)
            scores = np.asarray(scores)  # (chunk, K); valid where mask
            if n_rel is not None:
                n_rel = np.asarray(n_rel)
        with span("fed.record"):
            base = len(hist.round_loss)
            if n_rel is not None:
                hist.round_survivors.extend(int(x) for x in n_rel)
            hist.round_loss.extend(float(x) for x in losses)
            self._note_privacy(hist, len(mask))
            for r in np.nonzero(mask)[0]:
                self._append_eval(hist, base + int(r), scores[r], log_every)

    def _dispatch_round(self, hist: History, r: int, eval_mask,
                        log_every: int) -> None:
        """One per-round dispatch + metric append; shared by the loop
        driver and the scan driver's sub-chunk tail. Advances the carried
        key (chain identical to one scan step)."""
        with span("fed.round"):
            self.round_key, k_round, k_eval = jax.random.split(
                self.round_key, 3)
            (self.global_params, self.opt_states, self.ef_resid,
             self.fault_state, self.server_state, loss, n_rel) = self._round(
                self.global_params, self.opt_states, self.ef_resid,
                self.fault_state, self.server_state, k_round)
            hist.round_loss.append(float(loss))
            if n_rel is not None:
                hist.round_survivors.append(int(n_rel))
            self._note_privacy(hist, 1)
            if eval_mask[r]:
                scores = np.asarray(self._eval(self.global_params, k_eval))
                self._append_eval(hist, r, scores, log_every)

    def _recover_donated_opt_states(self) -> None:
        """After an interrupted block call the donated opt buffers may be
        consumed; rebuild them from the still-valid global params so the
        trainer stays usable (Adam moments reset, training state kept).
        Buffers that were never actually donated (e.g. interrupt during
        tracing, or a backend that ignores donation) are left alone.
        The donated EF residual recovers to zeros the same way (error
        feedback restarts; the global model is untouched), and the fault
        state to an empty one: the in-flight buffer is lost with the
        interrupt, and deterministic replay resumes from the carried
        round key."""
        if _deleted(self.opt_states):
            per_client = broadcast_to_clients(self.global_params,
                                              len(self.train_groups))
            self.opt_states = jax.vmap(self.opt.init)(per_client)
        if _deleted(self.ef_resid):
            self.ef_resid = jnp.zeros(self.ef_resid.shape, jnp.float32)
        if _deleted(self.fault_state):
            self.fault_state = av.init_fault_state(
                len(self.train_groups),
                tree_count_params(self.global_params))

    def _run_loop(self, rounds: int, log_every: int) -> History:
        hist = History()
        eval_mask = self._eval_mask(rounds)  # shared cadence, both drivers
        for r in range(rounds):
            self._dispatch_round(hist, r, eval_mask, log_every)
        return hist


# ---------------------------------------------------------------------------
# Engine 2: shard_map over the mesh client axis (TPU production / dry-run)
# ---------------------------------------------------------------------------
def make_sharded_round(gpo_cfg: GPOConfig, fed_cfg: FedConfig,
                       data: SurveyData, mesh, client_axes=("data",),
                       opt=None, agg: ServerAggregator | None = None
                       ) -> Callable:
    """Returns round_fn(client_params, opt_states, keys, group_ids,
    weights, server_state) -> (client_params, opt_states, losses,
    server_state).

    Client-carrying arguments have a leading *global* client axis sharded
    over ``client_axes``; ``server_state`` is replicated (every shard
    applies the same deterministic server update, DESIGN.md §7).
    Linear strategies reduce the client deltas with ONE weighted psum
    over those axes — the virtualized server; robust strategies
    all-gather the flattened delta shard and rank-trim locally (order
    statistics do not decompose into a psum). Multi-pod:
    client_axes=("pod", "data") gives hierarchical aggregation.
    With ``FedConfig.privacy`` enabled (DESIGN.md §9) each shard clips
    and noises its own clients' flat deltas LOCALLY — the per-client L2
    norm lives entirely within the client's shard, so no collective
    moves before the release point — and the round's single psum then
    carries the already-noised weighted sum (the robust family gathers
    the privatized matrix instead). Noise keys fold out of the
    per-client training ``keys``, so the round is bit-reproducible
    against the stacked engine given the same keys.
    For ``adaptive``, effective per-group weights are formed OUTSIDE the
    shard_map from the replicated scores (they need a normalization over
    all clients), so the mapped body stays collective-minimal.

    With ``FedConfig.compression`` enabled (DESIGN.md §10) each shard
    compresses its own clients' (privatized) flat deltas LOCALLY, after
    the DP release point: the linear family dequantizes shard-locally
    and keeps its ONE weighted psum; the robust family all-gathers the
    int8 payload + f32 per-client scales instead of f32 vectors (~4×
    fewer bytes on the round's dominant collective; ``dryrun.py
    --gpo-fed --compress int8`` prints the compiled byte counts). With
    ``error_feedback`` the round gains a trailing sharded
    ``resid (C_local, P)`` argument/result carrying the EF21 residual.
    Rounding uniforms fold out of the per-client training ``keys`` (the
    §9 noise-key scheme), so the round stays bit-reproducible against
    the stacked engine given the same keys.
    """
    from jax.sharding import PartitionSpec as P

    gpo_cfg = fed_cfg.resolve_gpo(gpo_cfg)  # runtime attention override
    fed_cfg.privacy.validate()
    fed_cfg.compression.validate()
    fed_cfg.adversary.validate()
    fed_cfg.hierarchy.validate(fed_cfg.num_clients)
    if fed_cfg.hierarchy.enabled:
        # the two-hop schedule (§14) needs a leading 'edge' mesh axis of
        # exactly num_edges shards in front of the intra-edge client
        # axes — build the mesh with launch.mesh.make_edge_mesh
        if (len(client_axes) < 2
                or mesh.shape[client_axes[0]] != fed_cfg.hierarchy.num_edges):
            raise ValueError(
                f"hierarchy.num_edges={fed_cfg.hierarchy.num_edges} "
                f"requires client_axes=('edge', ...) with a leading axis "
                f"of that size; got {tuple(client_axes)} on mesh "
                f"{dict(mesh.shape)}")
    byz.check_defense_composition(fed_cfg)
    priv = fed_cfg.privacy
    comp = fed_cfg.compression
    ef = comp.enabled and comp.error_feedback
    opt = opt or adam(fed_cfg.lr)
    if agg is None:
        agg = make_aggregator(fed_cfg.agg, num_clients=fed_cfg.num_clients,
                              use_pallas=fed_cfg.use_pallas_aggregation)
    local_train = _make_local_train(gpo_cfg, fed_cfg, data, opt)
    # the same declared stage pipeline as the stacked engine (DESIGN.md
    # §13): this body keeps the client layout and collective placement,
    # the pipeline owns the stage dispatch. With the adversary enabled
    # the round gains a trailing REPLICATED ``byz_key`` argument (the
    # launcher folds it from the round key) — the attack-off signature,
    # trace, and collective schedule are unchanged.
    pipe = make_pipeline(fed_cfg, agg=agg, num_clients=fed_cfg.num_clients)
    adv_on = fed_cfg.adversary.enabled
    axes = tuple(client_axes)
    spec = P(axes)
    repl = P()

    def _shard_gids(c_local):
        """This shard's global client ids, from the static mesh shape —
        no collective."""
        shard = 0
        for a in axes:
            shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
        return shard * c_local + jnp.arange(c_local, dtype=jnp.int32)

    def round_body(client_params, opt_states, keys, group_ids, weights,
                   server_state, resid=None, byz_key=None):
        # local shard: (C_local, ...) clients; train without collectives
        gids = _shard_gids(keys.shape[0]) if adv_on else None
        train_args = (client_params, opt_states, keys, group_ids)
        if pipe.flip_data:
            train_args += (pipe.attacked_flags(byz_key, gids),)
        with jax.named_scope("local_train"):
            new_params, new_opt, losses = jax.vmap(local_train)(*train_args)
        # delta contract: entry params ARE the replicated global model
        deltas = tree_sub(new_params, client_params)
        global_prev = tree_index(client_params, 0)
        # pipeline stages 2-5 head: [attack →] privacy → codec → reduce
        # collective (ONE weighted psum for the linear family, an
        # all-gather of rows for the robust one — see
        # RoundPipeline.sharded_delta for the full dispatch).
        delta, new_resid = pipe.sharded_delta(
            deltas, weights, keys, global_prev, resid, axes,
            byz_key=byz_key, gids=gids)
        all_losses = (jax.lax.all_gather(losses, axes, axis=0, tiled=True)
                      if agg.needs_losses else None)
        # replicated server update: same inputs on every shard -> same
        # global model and state, no second parameter-sized collective.
        global_params, server_state = agg.apply(
            server_state, global_prev, delta, losses=all_losses, idx=None)
        # redistribute: every client's next-round start is the global model
        c_local = keys.shape[0]
        client_params = broadcast_to_clients(global_params, c_local)
        return client_params, new_opt, losses, server_state, new_resid

    # ----------------------------------------------------------------------
    # Fault-aware sharded round (DESIGN.md §11). The schedule is derived
    # REPLICATED on every shard from the replicated ``fault_key`` + the
    # static client count — no collective is spent agreeing on who
    # failed — and ``weights`` arrive replicated (full (C,)) so the
    # survivor-mass renormalization is also computed redundantly per
    # shard. Only the in-flight straggler payloads (``FaultState.
    # pending``, the one parameter-sized leaf) are sharded with their
    # clients. Net effect: the linear family keeps its ONE psum with
    # byte-identical shape (survivor weights are zeroed, lost rows
    # contribute 0·row); the robust family keeps its single (C, P) f32
    # all-gather of the combined contribution rows (under compression
    # this forgoes the int8 wire layout — buffered arrivals are stored
    # decompressed, so the fault path gathers f32; dryrun --faults
    # reports the realized bytes).
    avail = fed_cfg.avail

    def fault_round_body(client_params, opt_states, keys, group_ids,
                         weights, server_state, fault, fault_key,
                         resid=None, byz_key=None):
        c_local = keys.shape[0]
        num_clients = weights.shape[0]  # replicated full population
        gids = _shard_gids(c_local)
        sched = av.round_schedule(fault_key, fault, avail, num_clients)
        train_args = (client_params, opt_states, keys, group_ids)
        if pipe.flip_data:
            train_args += (pipe.attacked_flags(byz_key, gids),)
        with jax.named_scope("local_train"):
            new_params, new_opt, losses = jax.vmap(local_train)(*train_args)
        deltas = tree_sub(new_params, client_params)
        global_prev = tree_index(client_params, 0)
        fresh_l = sched.fresh[gids]
        keep_l = fresh_l | sched.straggle[gids]
        new_opt = jax.tree.map(
            lambda n, o: jnp.where(
                keep_l.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
            new_opt, opt_states)
        # shard-local per-client [attack →] privacy → codec release; EF
        # rows advance only where the client actually released (fresh or
        # straggler-sent). A Byzantine straggler's BUFFERED payload is
        # already corrupted — the attack rides §11's replay semantics.
        rel_l, new_r = pipe.release_rows(
            tree_ravel_clients(deltas), keys, resid,
            byz_key=byz_key, gids=gids, axes=axes)
        new_resid = (jnp.where(keep_l[:, None], new_r, resid)
                     if ef else None)
        # contribution weights: replicated-computable from the schedule
        w_eff = weights.astype(jnp.float32)
        disc = av.staleness_discount(sched.staleness,
                                     fed_cfg.agg.staleness_power)
        w_fresh = jnp.where(sched.fresh, w_eff, 0.0)
        w_arr = jnp.where(sched.arrive, fault.pending_weight * disc, 0.0)
        w_c = w_fresh + w_arr
        mask_c = w_c > 0.0
        n_released = (jnp.sum(sched.fresh.astype(jnp.int32))
                      + jnp.sum(sched.arrive.astype(jnp.int32)))
        any_surv = n_released > 0
        mass = jnp.sum(w_c)
        # local combined contribution rows (same float ops as the
        # stacked engine, sliced at this shard's global client ids)
        wf_l, wa_l, wc_l = w_fresh[gids], w_arr[gids], w_c[gids]
        contrib_l = jnp.where(
            (wc_l > 0.0)[:, None],
            (wf_l[:, None] * rel_l + wa_l[:, None] * fault.pending)
            / jnp.maximum(wc_l, 1e-12)[:, None], 0.0)
        # pipeline aggregate stage, degraded mode: norm bound clips the
        # blended rows, then linear keeps the shard-local partial sum +
        # ONE psum while robust/defense families all-gather the rows.
        delta = tree_unflatten_from_vector(
            pipe.masked_reduce_sharded(
                contrib_l, w_c, mask_c, gids, axes,
                trim_frac=fed_cfg.agg.trim_frac), global_prev)
        all_losses = (jax.lax.all_gather(losses, axes, axis=0, tiled=True)
                      if agg.needs_losses else None)
        kw = {}
        if agg.buffered:
            kw = dict(mass=mass, released=n_released.astype(jnp.float32))
        if agg.needs_losses:
            kw["mask"] = sched.fresh
        new_global, new_state = agg.apply(
            server_state, global_prev, delta, losses=all_losses, idx=None,
            **kw)
        new_global = av.tree_where(any_surv, new_global, global_prev)
        server_state = av.tree_where(any_surv, new_state, server_state)
        # advance the fault state: metadata replicated, payloads local
        r = fault.round
        strag_l, arr_l = sched.straggle[gids], sched.arrive[gids]
        pending_l = jnp.where(strag_l[:, None], rel_l,
                              jnp.where(arr_l[:, None], 0.0,
                                        fault.pending))
        fault = av.FaultState(
            round=r + 1,
            offline_until=jnp.where(
                sched.crashed, r + 1 + int(avail.rejoin_rounds),
                fault.offline_until),
            pending=pending_l,
            pending_due=jnp.where(
                sched.straggle, r + sched.delay,
                jnp.where(sched.arrive, av.NO_PENDING,
                          fault.pending_due)),
            pending_weight=jnp.where(
                sched.straggle, w_eff,
                jnp.where(sched.arrive, 0.0, fault.pending_weight)),
            pending_birth=jnp.where(sched.straggle, r,
                                    fault.pending_birth))
        client_params = broadcast_to_clients(new_global, c_local)
        return (client_params, new_opt, losses, server_state, fault,
                new_resid)

    faults = avail.enabled
    # positional spec assembly: the base signature per engine, then the
    # optional trailing args in fixed order — EF residual shard (spec),
    # then replicated Byzantine key. Attack-off keeps the exact pre-§13
    # tuples (and traces), so the lowered round is byte-identical.
    if faults:
        fault_spec = av.FaultState(
            round=repl, offline_until=repl, pending=spec,
            pending_due=repl, pending_weight=repl, pending_birth=repl)
        # weights replicated: every shard renormalizes the survivor mass
        # redundantly instead of spending a collective on it
        in_specs = [spec, spec, spec, spec, repl, repl, fault_spec, repl]
        out_specs = [spec, spec, spec, repl, fault_spec]
        inner, n_out = fault_round_body, 5
    else:
        in_specs = [spec, spec, spec, spec, spec, repl]
        out_specs = [spec, spec, spec, repl]
        inner, n_out = round_body, 4
    if ef:
        in_specs.append(spec)
        out_specs.append(spec)
    if adv_on:
        in_specs.append(repl)

    def body(*args):
        base, rest = args[:len(in_specs) - ef - adv_on], \
            args[len(in_specs) - ef - adv_on:]
        resid = rest[0] if ef else None
        bk = rest[-1] if adv_on else None
        out = inner(*base, resid=resid, byz_key=bk)
        return out if ef else out[:n_out]

    sharded = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                            out_specs=tuple(out_specs), check_vma=False)

    def round_fn(client_params, opt_states, keys, group_ids, weights,
                 server_state, *rest):
        weights = agg.weigh(server_state, weights, None)
        return sharded(client_params, opt_states, keys, group_ids, weights,
                       server_state, *rest)

    return round_fn
