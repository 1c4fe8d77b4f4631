"""Set-up, requests, warm-up, counters and the correctness check of the
token-text scoring cell (``drivers/closed_text.py``): a frozen
granite-4.0-h backbone embeds each request's candidate texts inside
``PreferenceServer.step``, and the GPO predictor scores them.

The configuration file holds the backbone's published settings under
their own key names (with this chip's cut), the predictor's sections of
``gpo-d4096``, and the serving engine's. The backbone's weights are the
benchmark's own, made from the seed in the layout of
``reference_granite_h.py`` (stacked per layer kind); the program is
handed the same arrays.
"""
from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import common, reference
from chipbench import reference_granite_h as ref_bb
from chipbench.flops import granite_h as bb_flops
from chipbench.serve_lib import gap_stats, program_params

KINDS = ("mamba", "attention")


def backbone_config(cfg: dict):
    """The program's ``ModelConfig`` of the configuration file's
    backbone: every published key, with this chip's cut."""
    from repro.configs import ATTN, MAMBA, ModelConfig

    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    if cfg["mamba_n_groups"] != 1 or (cfg["mamba_expand"] * d
                                      != cfg["mamba_n_heads"]
                                      * cfg["mamba_d_head"]):
        raise SystemExit("the program's Mamba-2 takes one group and "
                         "n_heads * d_head == expand * hidden_size")
    return ModelConfig(
        name=cfg["name"], family="hybrid", source=cfg["source"],
        num_layers=cfg["num_hidden_layers"], d_model=d,
        vocab_size=cfg["vocab_size"], num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"], head_dim=d // heads,
        use_rope=cfg["position_embedding_type"] != "nope",
        attn_scale=cfg["attention_multiplier"],
        d_ff=cfg["intermediate_size"],
        num_experts=cfg["num_local_experts_published"],
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=cfg["num_local_experts"], moe_capacity_factor=0.0,
        shared_expert_ff=cfg["shared_intermediate_size"],
        ssm_state_size=cfg["mamba_d_state"], ssm_expand=cfg["mamba_expand"],
        ssm_head_dim=cfg["mamba_d_head"], ssm_conv_width=cfg["mamba_d_conv"],
        ssm_chunk=cfg["ssd_chunk"],
        block_pattern=tuple(MAMBA if k == "mamba" else ATTN
                            for k in cfg["layer_types"]),
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=float(cfg["logits_scaling"]),
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["dtype"], activation_dtype=cfg["dtype"])


# ---------------------------------------------------------------------------
# backbone weights: stacked per layer kind, reference names
# ---------------------------------------------------------------------------
def _shapes(cfg: dict) -> dict:
    """(shape, init, fan-in or std) per weight, per layer kind; a
    matrix's std is 1/sqrt(fan-in)."""
    d, e_all = cfg["hidden_size"], cfg["num_local_experts_published"]
    held, ff = cfg["num_local_experts"], cfg["intermediate_size"]
    sff = cfg["shared_intermediate_size"]
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    d_in, width = h * p, cfg["mamba_d_conv"]
    conv = d_in + 2 * n
    q = cfg["num_attention_heads"] * (d // cfg["num_attention_heads"])
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    ws = cfg["weights"]
    ffn = {"ln2": ((d,), "norm", ws["norm_std"]),
           "router": ((d, e_all), "matrix", d),
           "w_gate": ((held, d, ff), "matrix", d),
           "w_up": ((held, d, ff), "matrix", d),
           "w_down": ((held, ff, d), "matrix", ff),
           "shared_gate": ((d, sff), "matrix", d),
           "shared_up": ((d, sff), "matrix", d),
           "shared_down": ((sff, d), "matrix", sff)}
    mamba = {"ln": ((d,), "norm", ws["norm_std"]),
             "in_proj": ((d, 2 * d_in + 2 * n + h), "matrix", d),
             "conv_w": ((width, conv), "matrix", width),
             "conv_b": ((conv,), "normal", ws["conv_bias_std"]),
             "dt_bias": ((h,), "dt_bias", 0),
             "A_log": ((h,), "a_log", 0),
             "D": ((h,), "ones", 0),
             "norm": ((d_in,), "norm", ws["norm_std"]),
             "out_proj": ((d_in, d), "matrix", d_in), **ffn}
    attention = {"ln": ((d,), "norm", ws["norm_std"]),
                 # q and k scaled up, so that scores under the published
                 # multiplier spread as a trained model's do
                 "wq": ((d, q), "matrix", d / ws["qk_gain"] ** 2),
                 "wk": ((d, kv), "matrix", d / ws["qk_gain"] ** 2),
                 "wv": ((d, kv), "matrix", d), "wo": ((q, d), "matrix", q),
                 **ffn}
    return {"mamba": mamba, "attention": attention}


def _leaf(key, shape, init, arg):
    f32 = jnp.float32
    if init == "matrix":
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape, f32) / math.sqrt(
            arg)
    elif init in ("norm", "normal"):
        x = arg * jax.random.normal(key, shape, f32)
    elif init == "dt_bias":  # softplus(dt_bias) log-uniform in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif init == "a_log":
        x = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    else:
        x = jnp.ones(shape, f32)
    return x


_leaf_jit = jax.jit(lambda key, shape, init, arg, dtype: _leaf(
    key, shape, init, arg).astype(dtype), static_argnums=(1, 2, 3, 4))


def make_backbone_weights(cfg: dict, seed: int) -> dict:
    """``embed``, ``final_norm`` and, per layer kind, each weight
    stacked over that kind's layers, on the device in the configuration's
    dtype; one jitted call per weight, so that no more than one weight's
    float32 draw is held at a time."""
    key = jax.random.PRNGKey(seed)
    ws, dt = cfg["weights"], cfg["dtype"]
    out = {"embed": _leaf_jit(jax.random.fold_in(key, 0),
                              (cfg["vocab_size"], cfg["hidden_size"]),
                              "normal", ws["embed_std"], dt),
           "final_norm": _leaf_jit(jax.random.fold_in(key, 1),
                                   (cfg["hidden_size"],), "norm",
                                   ws["norm_std"], dt)}
    i = 2
    for kind, shapes in _shapes(cfg).items():
        n = cfg["layer_types"].count(kind)
        out[kind] = {}
        for name, (shape, init, arg) in shapes.items():
            out[kind][name] = _leaf_jit(jax.random.fold_in(key, i),
                                        (n,) + shape, init, arg, dt)
            i += 1
    return out


def program_backbone(w: dict) -> dict:
    """The benchmark's backbone weights in the program's pytree (the
    same arrays)."""
    from repro.configs import ATTN, MAMBA
    from repro.models.attention import AttnParams
    from repro.models.moe import MoEParams
    from repro.models.ssm import MambaParams

    def ffn(lw):
        return {"ln2": lw["ln2"],
                "moe": MoEParams(lw["router"], lw["w_gate"], lw["w_up"],
                                 lw["w_down"]),
                "shared": {"w_gate": lw["shared_gate"],
                           "w_up": lw["shared_up"],
                           "w_down": lw["shared_down"]}}

    m, a = w["mamba"], w["attention"]
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "layers": {
                MAMBA: {"ln": m["ln"], "mamba": MambaParams(
                    *(m[f] for f in MambaParams._fields)), **ffn(m)},
                ATTN: {"ln1": a["ln"], "attn": AttnParams(
                    a["wq"], a["wk"], a["wv"], a["wo"], None, None, None,
                    None, None), **ffn(a)}}}


def reference_weights(w: dict, cfg: dict) -> dict:
    """The reference's layout, one dict per layer: slices of the stacks,
    each taken when the reference reaches its layer (a generator, for
    one pass)."""
    def layers():
        seen = {k: 0 for k in KINDS}
        for kind in cfg["layer_types"]:
            yield {name: a[seen[kind]] for name, a in w[kind].items()}
            seen[kind] += 1

    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "layers": layers()}


def text_lengths(rng, n, law: dict) -> np.ndarray:
    """Log-normal text lengths (median, log-sd), rounded and clipped."""
    x = law["median"] * np.exp(law["log_sd"] * rng.standard_normal(n))
    return np.clip(np.round(x), law["min"], law["max"]).astype(np.int32)


class TextRequest:
    __slots__ = ("ctx_x", "ctx_y", "tokens", "lens", "key")

    def __init__(self, ctx_x, ctx_y, tokens, lens, key):
        self.ctx_x, self.ctx_y, self.key = ctx_x, ctx_y, key
        self.tokens, self.lens = tokens, lens


class TextServing:
    """Everything the text cell builds before its window: the backbone
    and its weights, the survey's texts and their embeddings (by the
    server's own programs), the predictor trained on them, the server
    and the request pool."""

    def __init__(self, ctx):
        from repro.configs import GPOConfig, ServeConfig
        from repro.core.gpo import GPOLayer
        from repro.core.serving import PreferenceServer, Request as PRequest

        self.PRequest = PRequest
        cfg, tr = ctx.config, ctx.traffic
        self.cfg, self.tr = cfg, tr
        self.bcfg = backbone_config(cfg)  # the parent's program stops here
        self.model = cfg["model"]
        self.products = cfg["reference_products"]
        self.a = a = cfg["survey"]["num_options"]
        s = common.seed32(ctx.seed)
        self.backbone = make_backbone_weights(cfg, s)
        embedder = (self.bcfg, program_backbone(self.backbone))
        serve = {k: tuple(v) if isinstance(v, list) else v
                 for k, v in cfg["serve"].items()}
        self.scfg = ServeConfig(**serve)
        gcfg = GPOConfig(**self.model)

        survey = common.make_survey(cfg["survey"], self.model["d_embed"])
        self.prefs = np.asarray(survey["prefs"])
        self.mask = np.asarray(survey["mask"])
        n_q = self.prefs.shape[1]
        shape_rng = np.random.default_rng(tr["shape_seed"])
        self.rng = np.random.default_rng(s)
        law = tr["text_tokens"]
        self.survey_texts = self._texts(text_lengths(shape_rng, n_q * a, law))
        # the survey embedded once, by the programs the window runs
        untrained = program_params(common.make_weights(self.model, s),
                                   GPOLayer)
        unit, _ = PreferenceServer(untrained, gcfg, self.scfg, num_options=a,
                                   embedder=embedder).embed(self.survey_texts)
        self.phi = unit.reshape(n_q, a, -1)
        trained = dict(survey, phi=jnp.asarray(self.phi))
        self.weights = common.trained_weights(self.model, trained,
                                              cfg["served_weights"], s)
        self.server = PreferenceServer(
            program_params(self.weights, GPOLayer), gcfg, self.scfg,
            num_options=a, embedder=embedder)
        self.ctx_range = tuple(tr["ctx_questions"])
        self.pool = self._pool(tr, shape_rng, law)
        check_rng = np.random.default_rng(tr["shape_seed"] + 1)
        self.survey_check = check_rng.choice(
            len(self.survey_texts), tr["survey_check_texts"], replace=False)
        self.lengths = {}  # rid -> (ctx points, text lengths)
        self.requests = {}  # rid -> TextRequest, for the check
        self.next_rid = 0

    # -- texts and requests ------------------------------------------------
    def _texts(self, lens) -> list:
        """Token texts of the given lengths, ids from the seed."""
        return [self.rng.integers(0, self.cfg["vocab_size"], n,
                                  dtype=np.int32) for n in lens]

    def _pool(self, tr, shape_rng, law) -> list:
        """``tr["pool"]`` requests. Sizes (text lengths, context sizes per
        group) and groups come from ``shape_seed``, so every seed runs
        the same batches; token ids and the context questions from the
        seed."""
        n, a = tr["pool"], self.a
        g_all, d = self.mask.shape[0], self.phi.shape[-1]
        t_q = tr["tgt_questions"][0]
        lens = text_lengths(shape_rng, n * t_q * a, law).reshape(n, -1)
        m_sizes = shape_rng.integers(self.ctx_range[0], self.ctx_range[1] + 1,
                                     g_all)
        groups = shape_rng.integers(g_all, size=n)
        contexts = []
        for g in range(g_all):
            q = self.rng.choice(np.flatnonzero(self.mask[g]),
                                size=int(m_sizes[g]), replace=False)
            contexts.append((self.phi[q].reshape(-1, d),
                             self.prefs[g, q].reshape(-1).astype(np.float32)))
        pool = []
        for i in range(n):
            g = int(groups[i])
            texts = self._texts(lens[i])
            tokens = np.zeros((len(texts), int(lens[i].max())), np.int32)
            for j, t in enumerate(texts):
                tokens[j, :len(t)] = t
            pool.append(TextRequest(*contexts[g], tokens, lens[i],
                                    ("group", g)))
        return pool

    def make(self, due: float = 0.0):
        """The next request of the pool as the program's ``Request``."""
        rid = self.next_rid
        self.next_rid += 1
        r = self.pool[rid % len(self.pool)]
        self.lengths[rid] = (r.ctx_x.shape[0], r.lens)
        self.requests[rid] = r
        return self.PRequest(rid=rid, ctx_x=r.ctx_x, ctx_y=r.ctx_y,
                             tgt_tokens=r.tokens, tgt_lens=r.lens,
                             prefix_key=r.key, arrival=due)

    # -- warm-up -----------------------------------------------------------
    def warm_shapes(self):
        """One pass over the pool in the closed loop's order: every batch
        the window can run (it takes ``outstanding`` requests at a time
        and steps until none is queued, so batches are the pool's runs of
        ``max_batch``), each context prefilled at its first use, so
        every embed, prefill, placement and decode program of the window
        is compiled (or loaded) here."""
        srv, out = self.server, self.tr["outstanding"]
        for _ in range(0, len(self.pool), out):
            for _ in range(out):
                srv.submit(self.make())
            while srv.queue_depth:
                srv.step()
        srv.reset(clear_cache=False)

    # -- counters ----------------------------------------------------------
    def batch_counters(self, batches) -> dict:
        """Backbone model FLOPs of the real tokens of the requests in
        ``batches`` (``flops/granite_h.py``), and their texts and
        tokens."""
        flops, texts, tokens = 0.0, 0, 0
        for b in batches:
            for rid in b.rids:
                lens = self.lengths[rid][1]
                flops += sum(bb_flops.text(self.cfg, int(n)) for n in lens)
                texts += len(lens)
                tokens += int(np.sum(lens))
        return {"backbone_flops": flops, "texts": texts, "tokens": tokens}

    # -- correctness -------------------------------------------------------
    def sample(self, rids, n_sample: int, seed: int) -> list:
        """``n_sample`` of ``rids`` drawn from the seed, with the request
        of most text tokens first."""
        rids = sorted(rids)
        longest = max(rids, key=lambda r: int(np.sum(self.lengths[r][1])))
        rest = [r for r in rids if r != longest]
        rng = np.random.default_rng(common.seed32(seed) + 17)
        pick = rng.choice(rest, size=min(n_sample - 1, len(rest)),
                          replace=False)
        return [longest] + [int(r) for r in pick]

    def check_texts(self, pick) -> list:
        """The sampled requests' texts, then the fixed sample of survey
        texts."""
        out = []
        for rid in pick:
            r = self.requests[rid]
            out.extend(t[:n] for t, n in zip(r.tokens, r.lens))
        return out + [self.survey_texts[i] for i in self.survey_check]

    def reference_pooled(self, texts, control=None) -> np.ndarray:
        """Pooled embeddings before the norm (n, d) of the plain reference,
        one text at a time (padded on the right to the longest bucket:
        the reference is causal, so the padding moves nothing)."""
        width = self.scfg.text_buckets[-1]
        out = []
        for t in texts:
            tokens = np.zeros(width, np.int32)
            tokens[:len(t)] = t
            _, pooled = ref_bb.hidden_states(
                reference_weights(self.backbone, self.cfg), self.cfg, tokens,
                len(t), control=control)
            out.append(np.asarray(pooled))
        return np.stack(out)

    def reference_rows(self, pick, pooled, **kw) -> list:
        """The reference predictor's rows for ``pick``, its targets the
        (unit-normed) reference embeddings ``pooled`` (the first rows of
        ``pooled``, in ``check_texts`` order), its contexts the program's
        survey embeddings, as the configuration states its products, or
        in a control precision (``dtype``)."""
        if "dtype" not in kw:
            kw["precision"] = self.products
        unit = pooled / (np.linalg.norm(pooled, axis=-1, keepdims=True)
                         + 1e-6)
        reqs, i = [], 0
        for rid in pick:
            r = self.requests[rid]
            n = len(r.lens)
            reqs.append((r.ctx_x, r.ctx_y, unit[i:i + n].astype(np.float32)))
            i += n
        return reference.serve_rows(
            self.weights, self.model, self.a, reqs,
            m_pad=self.ctx_range[1] * self.a,
            t_pad=self.tr["tgt_questions"][1] * self.a, **kw)

    def check(self, served: dict, n_sample: int, seed: int) -> dict:
        """``emb_gap``: the widest relative gap (max-abs over max-abs)
        between the server's pooled embeddings before the norm, from its
        own embed programs, and the reference's, over the sampled
        requests' texts and the fixed survey sample. ``row_gap``: the
        widest gap between the served rows and the reference predictor's
        on the reference's target embeddings."""
        pick = self.sample(served, n_sample, seed)
        texts = self.check_texts(pick)
        _, prog = self.server.embed(texts)
        ref = self.reference_pooled(texts)
        rel = [float(np.max(np.abs(p - r)) / np.max(np.abs(r)))
               for p, r in zip(prog, ref)]
        rows = self.reference_rows(pick, ref)
        stats = gap_stats([served[r] for r in pick], rows)
        print(f"embedding gaps: widest {max(rel)}, mean {np.mean(rel)}; "
              f"row gaps: {stats}", file=sys.stderr, flush=True)
        return {"emb_gap": max(rel), "row_gap": stats["widest"]}
