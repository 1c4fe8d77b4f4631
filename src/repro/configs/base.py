"""Config system.

Every architecture in the assigned pool is expressed as a ``ModelConfig``;
the paper's own module is a ``GPOConfig``; the federated runtime is a
``FedConfig``.  Configs are frozen dataclasses so they can be closed over by
jitted functions and hashed as static arguments.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Optional, Tuple

from repro.utils.registry import Registry

# ---------------------------------------------------------------------------
# Block kinds
# ---------------------------------------------------------------------------
ATTN = "attn"  # full/windowed self-attention + MLP (dense or MoE)
MAMBA = "mamba"  # Mamba2 SSD block
GLOBAL = -1  # sentinel window: attend to everything (causal)


@dataclass(frozen=True)
class ModelConfig:
    """A single decoder (or encoder-decoder) LM backbone.

    The zoo is expressed with one config class: dense/GQA, MoE, SSM, hybrid,
    enc-dec, and embedding-input (VLM / audio) variants are all field
    combinations, which is what lets one `train_step` / `serve_step` and one
    sharding rule-set cover all ten assigned architectures.
    """

    name: str = "model"
    family: str = "dense"  # dense | moe | ssm | hybrid | vlm | audio
    source: str = ""  # citation for the assigned config

    # trunk
    num_layers: int = 2
    d_model: int = 256
    vocab_size: int = 1024

    # attention
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 64
    qkv_bias: bool = False
    qk_norm: bool = False
    attn_logit_softcap: Optional[float] = None  # gemma2-style soft capping
    final_logit_softcap: Optional[float] = None
    rope_theta: float = 10_000.0
    # False = no positional encoding in attention (NoPE: granite-4.0-h)
    use_rope: bool = True
    # attention score multiplier; None = 1 / sqrt(head_dim)
    attn_scale: Optional[float] = None
    rope_theta_global: Optional[float] = None  # gemma3: different theta for global
    # sliding-window pattern, cycled over attention layers. -1 == global.
    window_pattern: Tuple[int, ...] = (GLOBAL,)

    # MLP / MoE
    d_ff: int = 1024
    num_experts: int = 0  # 0 => dense MLP
    experts_per_token: int = 0
    router_aux_coef: float = 0.01
    # tokens dropped beyond capacity; 0 = dropless grouped experts
    moe_capacity_factor: float = 1.25
    # experts held on this chip (the first ``experts_held`` of
    # ``num_experts``; the router still scores all of them); 0 = all.
    # Needs the dropless layer.
    experts_held: int = 0
    # width of one always-on shared expert beside the routed ones; 0 = none
    shared_expert_ff: int = 0

    # SSM (Mamba2 / SSD)
    ssm_state_size: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv_width: int = 4
    ssm_chunk: int = 128

    # layer pattern: cycled to num_layers. ("attn",) pure transformer,
    # ("mamba",) pure SSM. Hybrid (zamba2) uses block_pattern plus
    # shared_attn_every (a single *shared-weight* attention block applied
    # after every k trunk layers, as in Zamba2). A pattern that mixes
    # "mamba" and "attn" (granite-4.0-h) gives every layer its own
    # weights and its own MLP/MoE after the mixer; num_layers is then a
    # whole number of periods.
    block_pattern: Tuple[str, ...] = (ATTN,)
    shared_attn_every: int = 0  # 0 => no shared block

    # encoder-decoder (whisper)
    is_encoder_decoder: bool = False
    enc_layers: int = 0
    enc_seq_len: int = 0  # fixed encoder length (e.g. 1500 audio frames)

    # input modality: "tokens" -> int32 token ids; "embeddings" -> the
    # modality frontend is a stub and the model consumes (B, S, d_model)
    # precomputed embeddings (VLM patch embeddings / audio frames).
    input_kind: str = "tokens"

    # numerics
    param_dtype: str = "bfloat16"
    activation_dtype: str = "bfloat16"

    # serving
    long_context_variant: bool = False  # pure-dense archs get a SWA override
    long_context_window: int = 4096
    # ring-buffer decode caches for sliding-window layers (periodic
    # local:global patterns): local layers allocate W slots instead of the
    # full context (§Perf optimization; off = paper-faithful baseline)
    ring_cache: bool = False

    # normalization
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    scale_embeddings: bool = False  # gemma: embed * sqrt(d_model)
    # granite: embed * embedding_multiplier, each residual branch *
    # residual_multiplier, logits / logits_scaling
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    use_post_norm: bool = False  # gemma2/3 sandwich norm

    # ---------------------------------------------------------------
    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_mixed(self) -> bool:
        """Mamba and attention layers, each with its own weights."""
        return (not self.shared_attn_every
                and {ATTN, MAMBA} <= set(self.block_pattern))

    @property
    def num_experts_held(self) -> int:
        return self.experts_held or self.num_experts

    def layer_kinds(self) -> Tuple[str, ...]:
        """Per-layer block kind, block_pattern cycled to num_layers."""
        p = self.block_pattern
        return tuple(p[i % len(p)] for i in range(self.num_layers))

    def attn_layer_windows(self, seq_hint: int = 0) -> Tuple[int, ...]:
        """Window size per *attention* layer (cycled window_pattern).

        GLOBAL (-1) stays -1; consumers replace it with the running sequence
        length. Ordering matches the order attention layers appear in
        ``layer_kinds()``.
        """
        n_attn = sum(1 for k in self.layer_kinds() if k == ATTN)
        p = self.window_pattern
        return tuple(p[i % len(p)] for i in range(n_attn))

    def validate(self) -> None:
        assert self.num_heads % max(self.num_kv_heads, 1) == 0, self.name
        if self.is_moe:
            assert 0 < self.experts_per_token <= self.num_experts, self.name
            assert 0 <= self.experts_held <= self.num_experts, self.name
            assert not self.experts_held or not self.moe_capacity_factor, (
                f"{self.name}: experts_held needs the dropless layer")
        if self.is_mixed:
            assert self.num_layers % len(self.block_pattern) == 0, self.name
        kinds = set(self.layer_kinds())
        if MAMBA in kinds:
            assert self.ssm_state_size > 0, self.name
        if self.is_encoder_decoder:
            assert self.enc_layers > 0 and self.enc_seq_len > 0, self.name


@dataclass(frozen=True)
class GPOConfig:
    """The paper's module: the transformer-based preference predictor.

    An in-context neural process (Zhao et al. 2023, GPO): inputs are
    (embedding, preference) context pairs and embedding-only targets; the
    model predicts the target preferences. PluralLLM trains this with
    FedAvg across groups.
    """

    d_embed: int = 64  # frozen-backbone embedding width (4096 for Alpaca-7B)
    d_model: int = 128
    num_layers: int = 4
    num_heads: int = 4
    d_ff: int = 256
    norm_eps: float = 1e-6
    # Gaussian likelihood: if learn_sigma the head emits (mu, log_sigma),
    # else sigma=1 and Eq. 1's NLL reduces to MSE (GPO's practice).
    learn_sigma: bool = False
    param_dtype: str = "float32"
    # use the Pallas neural-process attention kernel (interpret mode on
    # CPU; native on TPU) for BOTH inference and training: the kernel
    # carries a flash-style custom VJP (DESIGN.md §8), so gpo_loss under
    # jax.grad runs the banded forward/backward grids instead of the
    # dense masked-softmax einsum. False = jnp everywhere.
    use_pallas_attention: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads


@dataclass(frozen=True)
class PrivacyConfig:
    """Differential privacy on the client→server delta path (DESIGN.md §9).

    The pipeline sits BETWEEN local training and the ``ServerAggregator``:
    each client's flattened parameter delta is L2-clipped to ``clip_norm``
    and perturbed with per-client Gaussian noise of standard deviation
    ``noise_multiplier * clip_norm`` before any reduction, so it composes
    with every registry strategy (the robust trims rank the *privatized*
    deltas; the linear family reduces them — with
    ``use_pallas_aggregation`` through the fused ``agg_clip_reduce``
    kernel). ``clip_norm == 0`` disables the pipeline entirely: the
    engines trace the exact pre-privacy computation (bit-equal, pinned by
    tests/test_privacy.py).

    Privacy accounting is the Rényi-DP moments accountant
    (``core/privacy.py::RdpAccountant``): each round is one sampled
    Gaussian mechanism with sampling rate q = batch_groups/num_clients
    (1 under full participation), RDP composes linearly over rounds, and
    the per-round ε at ``target_delta`` lands in ``History.round_eps``.
    """

    # per-client L2 clip norm S on the flattened delta; 0.0 disables the
    # whole privacy pipeline (the exact pre-privacy trace)
    clip_norm: float = 0.0
    # Gaussian noise multiplier z: per-client noise std = z * clip_norm.
    # 0.0 = clip-only (no DP guarantee; History.round_eps reports inf).
    noise_multiplier: float = 0.0
    # the δ at which the accountant converts accumulated RDP to ε
    target_delta: float = 1e-5
    # Rényi orders α the accountant tracks (integer-order sampled-
    # Gaussian bound, Mironov et al. 2019)
    accountant_orders: Tuple[int, ...] = tuple(range(2, 33)) + (
        48, 64, 128, 256)

    @property
    def enabled(self) -> bool:
        return self.clip_norm > 0.0

    @property
    def sigma(self) -> float:
        """Per-client noise standard deviation (z * S)."""
        return self.noise_multiplier * self.clip_norm

    def validate(self) -> None:
        if self.clip_norm < 0.0 or self.noise_multiplier < 0.0:
            raise ValueError("clip_norm and noise_multiplier must be >= 0")
        if self.noise_multiplier > 0.0 and self.clip_norm == 0.0:
            raise ValueError(
                "noise_multiplier > 0 requires clip_norm > 0: the noise "
                "scale is z * clip_norm, and unclipped deltas have "
                "unbounded sensitivity (no finite-σ DP guarantee exists)")
        if not 0.0 < self.target_delta < 1.0:
            raise ValueError("target_delta must lie in (0, 1)")


@dataclass(frozen=True)
class AvailabilityConfig:
    """Client availability / failure simulator (DESIGN.md §11).

    Drives the fault-injection layer of the federated round
    (``core/availability.py``): per-round, per-client Bernoulli draws —
    folded out of a per-round fault key, so the failure *schedule* is a
    deterministic function of the seed and bit-identical across the
    scan, loop, and sharded engines — decide which clients are offline,
    which crash after local training (update lost before release), and
    which straggle (their update arrives ``delay`` ∈ [1, max_staleness]
    rounds late and is aggregated with a polynomial staleness discount
    by buffered strategies). Crashed clients stay offline for
    ``rejoin_rounds`` rounds before rejoining (crash-rejoin traces).

    All of it is expressed as per-round masks / staleness vectors that
    live INSIDE the jitted round (no Python-side branching), so the
    fused ``lax.scan`` driver replays identical failure schedules.
    The default (everything benign) disables the layer *statically*:
    the engines trace the exact pre-fault computation, bit-equal to a
    default run (pinned by tests/test_availability.py, the
    privacy/compression degeneracy-pin style).
    """

    # per-round probability a client is reachable at all. 1.0 = always
    # online (disables the availability draw).
    online_prob: float = 1.0
    # probability an online client crashes AFTER local training: the
    # update is lost before release (EF residual untouched, opt state
    # reverts — the machine died), and the client stays offline for
    # ``rejoin_rounds`` further rounds.
    crash_prob: float = 0.0
    # probability an online, non-crashed client is a straggler: its
    # released update arrives ``delay`` rounds late, delay uniform in
    # [1, max_staleness]. While an upload is in flight the client is
    # busy (it does not start a new round).
    straggler_prob: float = 0.0
    # staleness bound: the largest delay a straggler update can have.
    max_staleness: int = 0
    # rounds a crashed client stays offline before rejoining.
    rejoin_rounds: int = 0

    @property
    def enabled(self) -> bool:
        return (self.online_prob < 1.0 or self.crash_prob > 0.0
                or self.straggler_prob > 0.0)

    def release_rate(self) -> float:
        """Per-round probability an (independently) sampled client's
        update is eventually released: online ∧ no crash. Stragglers DO
        release (late), so they count; the crash-rejoin and busy-while-
        in-flight dynamics only lower availability further, so this is
        an upper bound — the conservative direction for the §9 RDP
        accountant (a larger q never under-reports ε)."""
        if not self.enabled:
            return 1.0
        return self.online_prob * (1.0 - self.crash_prob)

    def validate(self) -> None:
        if not 0.0 <= self.online_prob <= 1.0:
            raise ValueError("online_prob must lie in [0, 1]")
        if not 0.0 <= self.crash_prob <= 1.0:
            raise ValueError("crash_prob must lie in [0, 1]")
        if not 0.0 <= self.straggler_prob <= 1.0:
            raise ValueError("straggler_prob must lie in [0, 1]")
        if self.max_staleness < 0 or self.rejoin_rounds < 0:
            raise ValueError(
                "max_staleness and rejoin_rounds must be >= 0")
        if self.straggler_prob > 0.0 and self.max_staleness < 1:
            raise ValueError(
                "straggler_prob > 0 requires max_staleness >= 1: a "
                "straggler's delay is drawn from [1, max_staleness]")


@dataclass(frozen=True)
class AdversaryConfig:
    """Byzantine adversarial-client simulator (DESIGN.md §13).

    Drives the attack-injection layer of the federated round
    (``core/adversary.py``): per round, exactly ``num_attackers``
    clients are marked Byzantine by draws folded out of a per-round
    Byzantine key — the attacker *schedule* is a deterministic function
    of (seed, round, client index) and bit-identical across the scan,
    loop, and sharded engines — and their released deltas (or, for
    ``label_flip``, their local training data) are corrupted before the
    privacy/codec/aggregation stages see them. The threat model is the
    strongest standard one: attackers are omniscient colluders who know
    the honest updates of the round (``alie`` uses their empirical
    moments), but the server-side defenses (krum / multi_krum /
    geomedian / norm_bound, DESIGN.md §13) never learn which clients
    are corrupt.

    The default (``kind="none"``) disables the layer *statically*: the
    engines trace the exact pre-attack computation, bit-equal to a
    pre-PR run (pinned by tests/test_adversary.py, the availability /
    privacy / compression degeneracy-pin style).
    """

    # none | sign_flip | scaled | gaussian | alie | label_flip
    kind: str = "none"
    # Byzantine population size f: exactly f clients (re-drawn each
    # round) attack. Defenses tolerate f below their breakdown point
    # (krum/multi_krum need f <= C - 3 selectable, robust f < C/2).
    num_attackers: int = 0
    # scaled model-replacement factor λ: attacker ships λ·d (λ large
    # drags a mean-style aggregator toward the malicious direction).
    scale: float = 10.0
    # additive Gaussian attack: per-coordinate noise std added to the
    # attacker's honest delta.
    noise_std: float = 1.0
    # ALIE (Baruch et al. 2019): colluding attackers all ship
    # mean_honest + z · std_honest per coordinate — inside the honest
    # spread, so distance-based defenses struggle; z is the deviation.
    alie_z: float = 1.0

    @property
    def enabled(self) -> bool:
        return self.kind != "none" and self.num_attackers > 0

    @property
    def data_level(self) -> bool:
        """Attack corrupts the local training data, not the released
        delta (the delta-stage attack transform is the identity)."""
        return self.kind == "label_flip"

    def validate(self) -> None:
        kinds = ("none", "sign_flip", "scaled", "gaussian", "alie",
                 "label_flip")
        if self.kind not in kinds:
            raise ValueError(
                f"adversary kind {self.kind!r} must be one of {kinds}")
        if self.num_attackers < 0:
            raise ValueError("num_attackers must be >= 0")
        if self.noise_std < 0.0:
            raise ValueError("noise_std must be >= 0")


@dataclass(frozen=True)
class CompressionConfig:
    """Client→server delta-compression stage (DESIGN.md §10).

    Sits BETWEEN the privacy pipeline and the ``ServerAggregator``: the
    (possibly privatized) flat client delta is compressed AFTER the DP
    release — compression is post-processing of the released value, so ε
    is unaffected — and the server consumes the decompressed
    ("transmitted") values. Two codecs:

    * ``int8`` — per-client symmetric quantization: scale s_c =
      max|d_c| / 127, values stochastically rounded to int8 (unbiased:
      E[Q(x)] = x; ``stochastic=False`` rounds to nearest). On the
      sharded engine the robust-aggregator family all-gathers the int8
      payload + f32 scales instead of f32 vectors (~4× fewer collective
      bytes); the linear family dequantizes shard-locally before its
      unchanged one-psum.
    * ``topk`` — magnitude sparsification: per client, entries with
      |d_c[p]| below the ⌈topk_frac·P⌉-th largest magnitude are zeroed
      (ties at the threshold are kept, so at least k survive).

    ``error_feedback`` carries an EF21-style per-client residual
    e_c ← (d̃_c + e_c) − Q(d̃_c + e_c) in the round state (the fused
    scan carry, next to ``AggState``), so compression error accumulates
    into later rounds instead of being lost — the standard fix for
    biased codecs like top-k. ``kind="none"`` (default) disables the
    stage entirely: the engines statically trace the exact
    pre-compression computation (bit-equal, pinned by
    tests/test_compression.py).
    """

    kind: str = "none"  # none | int8 | topk
    # topk: fraction of the flattened parameter axis kept per client
    topk_frac: float = 0.01
    # EF21-style error-feedback residual carried across rounds
    error_feedback: bool = True
    # int8: stochastic rounding (unbiased) vs round-to-nearest
    stochastic: bool = True

    @property
    def enabled(self) -> bool:
        return self.kind != "none"

    @property
    def needs_rng(self) -> bool:
        """The codec draws per-client randomness (stochastic rounding)."""
        return self.kind == "int8" and self.stochastic

    def validate(self) -> None:
        if self.kind not in ("none", "int8", "topk"):
            raise ValueError(
                f"compression kind {self.kind!r} must be one of "
                "'none' | 'int8' | 'topk'")
        if self.kind == "topk" and not 0.0 < self.topk_frac <= 1.0:
            raise ValueError(
                f"topk_frac={self.topk_frac} must lie in (0, 1]")


@dataclass(frozen=True)
class ServeConfig:
    """Multi-tenant reward-model serving engine (DESIGN.md §12).

    Drives ``core/serving.py::PreferenceServer``: a FIFO request queue
    with admission control, a continuous batcher that pads ragged
    context/target lengths to a small static *bucket* set (so the
    jitted ``prefill``/``decode`` shape family stays compile-cached), an
    LRU prefix cache of per-layer context K/V keyed on the shared ICL
    context (hits skip prefill entirely and are bit-equal to the cold
    path — the neural-process mask makes the context encoding exactly
    target-independent), and an optional int8 weight-only inference
    path that quantizes checkpoint weights at load time with the §10
    symmetric-quantization contract.
    """

    # largest number of requests fused into one decode dispatch
    max_batch: int = 8
    # padded batch sizes: the batcher pads a partial batch up to the
    # smallest bucket >= its size (dummy rows, sliced off) so the
    # compiled shape set is the bucket list, not every integer <= max
    batch_buckets: Tuple[int, ...] = (1, 2, 4, 8)
    # padded context / target lengths in POINTS (m questions x A
    # options); requests pad to the smallest bucket that fits. Target
    # buckets must be multiples of the survey's num_options so padded
    # rows reshape into whole questions.
    ctx_buckets: Tuple[int, ...] = (40, 80, 160)
    tgt_buckets: Tuple[int, ...] = (20, 40, 80, 160)
    # admission control: submissions beyond this queue depth are
    # rejected (the caller sees backpressure instead of unbounded
    # latency). 0 = unbounded.
    max_queue: int = 128
    # prefix-cache capacity in entries (LRU eviction); 0 disables the
    # cache (every request prefills — the benchmark cold baseline).
    # Every entry is stored at the largest ctx bucket (zero rows past
    # its own): 2 x num_layers x ctx_buckets[-1] x d_model x 4 bytes,
    # 655 KB for the default 4-layer, d_model=128 predictor, so 256 entries
    # hold at most 168 MB of device memory.
    cache_entries: int = 256
    # quantize the predictor's dense weights to int8 at load time and
    # serve through the fused int8 matmul kernel (DESIGN.md §12)
    int8_weights: bool = False
    # requests whose targets are token texts: texts share backbone rows.
    # A step's texts are packed (first-fit decreasing) into rows whose
    # length is the smallest text bucket that holds the longest text, at
    # most row length // text_buckets[0] texts a row (4 at 512 of 128),
    # kept apart by segment ids; the row count pads to a row bucket (a
    # step needing more rows than the largest splits). The embed
    # programs are the row lengths x row counts of these. An embedder
    # with no segment path (encoder-decoder, shared-attention hybrid)
    # takes one text a row, grouped by its own length bucket.
    text_buckets: Tuple[int, ...] = (128, 256, 512)
    text_row_buckets: Tuple[int, ...] = (12, 20, 28)

    def validate(self) -> None:
        for name, buckets in (("batch_buckets", self.batch_buckets),
                              ("ctx_buckets", self.ctx_buckets),
                              ("tgt_buckets", self.tgt_buckets),
                              ("text_buckets", self.text_buckets),
                              ("text_row_buckets", self.text_row_buckets)):
            if not buckets or list(buckets) != sorted(set(buckets)):
                raise ValueError(
                    f"{name} must be non-empty strictly ascending, got "
                    f"{buckets}")
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if self.max_batch > self.batch_buckets[-1]:
            raise ValueError(
                f"max_batch={self.max_batch} exceeds the largest batch "
                f"bucket {self.batch_buckets[-1]}")
        if self.max_queue < 0 or self.cache_entries < 0:
            raise ValueError("max_queue and cache_entries must be >= 0")


@dataclass(frozen=True)
class AggConfig:
    """Server-aggregation strategy (DESIGN.md §7).

    The paper's Eq. 2-3 FedAvg is ``name="fedavg"`` with the defaults
    below. Every other strategy consumes the same client payload — the
    parameter *delta* each client produced this round — and differs only
    in the stateful server update applied to the weighted delta moment
    (momentum / Adam / Yogi), in the reduction itself (rank-trimmed mean,
    coordinate-wise median), or in how the per-group weights are formed
    (APPA-style fairness-adaptive weights). ``prox_mu`` is the one
    client-side knob: a FedProx proximal term added to the local
    objective, independent of the server rule.
    """

    # registry name: fedavg | fedavgm | fedadam | fedyogi | fedprox |
    # trimmed_mean | median | adaptive | fedbuff | krum | multi_krum |
    # geomedian  (repro.core.aggregation)
    name: str = "fedavg"
    # server learning rate on the aggregated delta (1.0 == paper FedAvg)
    server_lr: float = 1.0
    # fedavgm: server momentum on the delta moment (0.0 degenerates to
    # fedavg exactly)
    momentum: float = 0.9
    # fedadam / fedyogi (Reddi et al. 2021): first/second-moment decays
    # and the adaptivity floor tau. (beta1=0, beta2=1, tau=1) degenerates
    # to fedavg exactly (v stays 0, the update is delta / (0 + 1)).
    beta1: float = 0.9
    beta2: float = 0.99
    tau: float = 1e-3
    # fedprox client-side proximal coefficient mu: local loss grows
    # (mu/2) * ||theta - theta_global||^2. 0.0 == plain local Adam.
    prox_mu: float = 0.0
    # trimmed_mean: fraction of clients trimmed at EACH end of the
    # per-coordinate ranking (k = floor(frac * C), clamped to 2k < C).
    # 0.0 degenerates to the weighted mean exactly.
    trim_frac: float = 0.1
    # adaptive (APPA-style): per-group weights  w_g ∝ p_g * exp(temp *
    # (score_g - mean score))  where score_g is an EMA of the group's
    # local loss — groups the global model serves worst get upweighted,
    # driving the fairness-index metric. temp=0.0 degenerates to the
    # dataset-size weights exactly.
    fair_temp: float = 1.0
    fair_decay: float = 0.9
    # fedbuff (FedBuff-style staleness-aware buffered aggregation,
    # DESIGN.md §11): the server accumulates released client updates in
    # a buffer and applies one server step only once ``buffer_k`` fresh-
    # enough updates have arrived. buffer_k=1 flushes every round and
    # degenerates to fedavg exactly (given full participation).
    buffer_k: int = 4
    # polynomial staleness discount s(τ) = (1 + τ)^(-staleness_power)
    # applied to updates arriving τ rounds late (FedBuff's 1/sqrt(1+τ)
    # at the 0.5 default). The fault-aware round discounts late
    # arrivals for EVERY strategy through this knob; 0.0 recovers the
    # classic synchronous baseline that lands stale deltas at full
    # weight — the failure mode fedbuff's discounted buffering exists
    # to fix.
    staleness_power: float = 0.5
    # krum / multi_krum (Blanchard et al. 2017): the number of Byzantine
    # clients the selection must tolerate. Each client is scored by the
    # sum of its (n - f - 2) smallest squared distances to the others;
    # krum returns the single lowest-scoring delta, multi_krum the
    # weighted mean of the ``multi_krum_m`` lowest. Breakdown point:
    # selection is sound while 2f + 2 < n.
    num_malicious: int = 0
    multi_krum_m: int = 3
    # geomedian: smoothed Weiszfeld iterations and the smoothing floor
    # eps on the per-client distances (jit-stable fixed iteration count;
    # Pillutla et al. 2022). Breakdown point 1/2 of the weight mass.
    geomedian_iters: int = 8
    geomedian_eps: float = 1e-6
    # server-side per-client L2 norm bound (DESIGN.md §13): each
    # client's released delta row is clipped to this norm BEFORE the
    # reduce, bounding any single client's pull on a linear aggregate.
    # Composes with every strategy; 0.0 disables (bit-equal paths).
    norm_bound: float = 0.0


@dataclass(frozen=True)
class HierarchyConfig:
    """Two-level client→edge→server aggregation topology (DESIGN.md §14).

    ``num_edges`` E partitions the round's participants into E contiguous
    edge shards (edge e owns client rows [e·C/E, (e+1)·C/E)); each edge
    pre-reduces its own clients before a cross-edge reduction produces
    the server update:

    * linear family — per-edge weighted partial sums, summed across
      edges (the same weighted moment, reassociated edge-first; the
      sharded engine keeps its single psum, which IS the composed
      two-hop on a real torus).
    * robust family — each edge runs the server rule over its OWN
      clients (per-edge trim / edge-local krum candidate selection) to
      one candidate row, then the same rule runs over the E candidates
      weighted by edge mass. The sharded engine's all-gather splits into
      an intra-edge hop (C/E rows) plus a cross-edge hop of only E
      candidate rows — O(E·P) instead of O(C·P) — and the cross-edge
      hop carries the §10 int8 wire layout when the codec is on. The
      breakdown point changes: attackers concentrated in one edge can
      capture its candidate (see §14).

    ``num_edges == 1`` disables the topology entirely: the pipeline's
    flat aggregate stage is traced unchanged (bit-equal, pinned by
    tests/test_hierarchy.py). Divisibility of the participant count by
    ``num_edges`` is checked by the engines, where it is known.
    """

    num_edges: int = 1

    @property
    def enabled(self) -> bool:
        return self.num_edges > 1

    def validate(self, num_clients: Optional[int] = None) -> None:
        if self.num_edges < 1:
            raise ValueError("num_edges must be >= 1")
        if (num_clients is not None and self.enabled
                and num_clients % self.num_edges != 0):
            raise ValueError(
                f"hierarchy.num_edges={self.num_edges} must divide the "
                f"round's participant count ({num_clients}): edges are "
                "contiguous equal-size client shards")


@dataclass(frozen=True)
class FedConfig:
    """PluralLLM federated runtime (paper §3.1–3.2, §4.3)."""

    num_clients: int = 10  # |G_train|
    num_eval_groups: int = 7  # |G_eval| (60/40 split in the paper)
    rounds: int = 1300  # communication rounds (paper: 1300)
    local_epochs: int = 6  # paper: 6 local epochs per round
    lr: float = 3e-4  # paper: Adam 3e-4
    eval_every: int = 10  # paper: every 10 rounds
    # in-context split per local epoch
    num_context: int = 16  # m context points
    num_target: int = 16  # n - m target points
    batch_groups: int = 0  # 0 => all clients participate each round (paper)
    # aggregate with the Pallas reduction kernels on the flattened
    # (C, P) client-delta matrix instead of the per-leaf jnp reductions
    # (same math either way; see DESIGN.md §4, §7). Applies to both the
    # vmapped and the shard_map engines.
    use_pallas_aggregation: bool = False
    # server-aggregation strategy (DESIGN.md §7); the default AggConfig
    # is the paper's Eq. 2-3 FedAvg.
    agg: AggConfig = AggConfig()
    # differential privacy on the client→server deltas (DESIGN.md §9):
    # per-client L2 clip + Gaussian noise applied BEFORE the aggregator,
    # with Rényi-DP accounting into History.round_eps. The default
    # (clip_norm=0) traces the exact pre-privacy computation.
    privacy: PrivacyConfig = PrivacyConfig()
    # client→server delta compression (DESIGN.md §10): int8 stochastic
    # quantization or top-k sparsification with an EF21-style error-
    # feedback residual, applied AFTER the DP release and BEFORE the
    # aggregator. The default (kind="none") traces the exact
    # pre-compression computation.
    compression: CompressionConfig = CompressionConfig()
    # client availability / failure simulation (DESIGN.md §11): per-
    # round offline/crash/straggler masks with deterministic fold-out
    # keys, a staleness buffer for late arrivals, and graceful-
    # degradation semantics for every aggregation strategy. The default
    # (everything benign) traces the exact pre-fault computation.
    avail: AvailabilityConfig = AvailabilityConfig()
    # Byzantine adversarial-client simulation (DESIGN.md §13): per-
    # round attacker masks with deterministic fold-out keys and delta-
    # or data-level corruption injected between local training and the
    # privacy/codec/aggregation stages. The default (kind="none")
    # traces the exact pre-attack computation.
    adversary: AdversaryConfig = AdversaryConfig()
    # two-level client→edge→server aggregation topology (DESIGN.md §14):
    # num_edges edge shards pre-reduce their clients before the cross-
    # edge reduction — the robust family's dominant all-gather shrinks
    # from O(C·P) to O(E·P) cross-edge, multiplicative with the §10 int8
    # wire layout. The default (num_edges=1) traces the exact flat
    # aggregate stage.
    hierarchy: HierarchyConfig = HierarchyConfig()
    # hard-error instead of warning when a configuration leaks
    # un-privatized client statistics around the DP release — today:
    # agg.name == "adaptive" keeps raw-loss EMAs (DESIGN.md §9) while
    # noise_multiplier > 0 promises a DP guarantee on the deltas.
    strict_privacy: bool = False
    # runtime-level override of GPOConfig.use_pallas_attention: None
    # defers to the model config; True/False forces the attention path
    # for every engine built from this FedConfig (FederatedGPO,
    # make_sharded_round, CentralizedGPO, the --gpo-fed dryrun) without
    # editing the model config it was handed.
    use_pallas_attention: Optional[bool] = None
    seed: int = 0

    def resolve_gpo(self, gpo_cfg: GPOConfig) -> GPOConfig:
        """GPOConfig with this runtime's overrides applied — the single
        plumbing point every training engine calls before tracing."""
        if (self.use_pallas_attention is not None
                and self.use_pallas_attention
                != gpo_cfg.use_pallas_attention):
            gpo_cfg = replace(
                gpo_cfg, use_pallas_attention=self.use_pallas_attention)
        return gpo_cfg


@dataclass(frozen=True)
class TrainConfig:
    """Generic backbone training (LM objective) settings."""

    global_batch: int = 8
    seq_len: int = 128
    steps: int = 10
    lr: float = 3e-4
    warmup_steps: int = 10
    weight_decay: float = 0.0
    grad_clip: float = 1.0
    seed: int = 0
    # remat policy for the layer scan: "none" | "full" | "dots"
    remat: str = "none"


@dataclass(frozen=True)
class InputShape:
    """One of the four assigned workload shapes."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES: dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}

# registry: arch id -> ModelConfig factory
ARCHITECTURES: Registry = Registry("architecture")


def get_arch(name: str) -> ModelConfig:
    cfg = ARCHITECTURES.get(name)
    cfg.validate()
    return cfg


def smoke_variant(cfg: ModelConfig) -> ModelConfig:
    """Reduced variant of the same family: 2 layers, d_model<=512,
    <=4 experts — runnable on CPU in a test. A mixed Mamba/attention
    pattern keeps one layer of each kind, in the order they first
    appear."""
    updates = dict(
        num_layers=2,
        d_model=min(cfg.d_model, 256),
        num_heads=4,
        num_kv_heads=min(4, max(1, cfg.num_kv_heads)),
        head_dim=64,
        d_ff=min(cfg.d_ff, 512) if cfg.d_ff else 0,
        vocab_size=min(cfg.vocab_size, 512),
        param_dtype="float32",
        activation_dtype="float32",
    )
    if cfg.is_moe:
        updates.update(num_experts=4, experts_per_token=min(2, cfg.experts_per_token),
                       experts_held=0)
        if cfg.moe_capacity_factor:
            updates.update(moe_capacity_factor=4.0)  # drop-free for exact tests
    if cfg.shared_expert_ff:
        updates.update(shared_expert_ff=min(cfg.shared_expert_ff, 512))
    if cfg.is_mixed:
        updates.update(block_pattern=tuple(dict.fromkeys(cfg.block_pattern)))
    if cfg.ssm_state_size:
        updates.update(ssm_state_size=min(cfg.ssm_state_size, 32), ssm_head_dim=32,
                       ssm_chunk=16)
    if cfg.is_encoder_decoder:
        updates.update(enc_layers=2, enc_seq_len=32)
    if cfg.shared_attn_every:
        updates.update(shared_attn_every=2)
    if len(cfg.window_pattern) > 1 or cfg.window_pattern[0] != GLOBAL:
        # keep the local/global alternation but shrink windows
        updates.update(
            window_pattern=tuple(min(w, 16) if w > 0 else w for w in cfg.window_pattern)
        )
    out = replace(cfg, name=cfg.name + "-smoke", **updates)
    out.validate()
    return out


def override(cfg, **kw):
    """Dataclass-replace with validation (public config-override hook)."""
    out = replace(cfg, **kw)
    if isinstance(out, ModelConfig):
        out.validate()
    return out


def config_dict(cfg) -> dict:
    return dataclasses.asdict(cfg)
