"""Multi-tenant serving engine for the GPO preference predictor
(DESIGN.md §12).

The trained predictor is the paper's product: a group-conditioned reward
model answering "what would group g answer to question q?" under real
query load. This module turns the single-tenant, synchronous
``predict_preferences`` call into a serving engine:

* **Queue + admission** — ``submit`` appends to a FIFO queue bounded by
  ``ServeConfig.max_queue``; over-capacity submissions are *rejected*
  (backpressure) instead of growing tail latency without bound.
* **Continuous batching over ragged lengths** — each engine ``step``
  fuses up to ``max_batch`` head-of-line requests into one decode
  dispatch. Requests carry ragged (context, target) lengths; the batcher
  pads them to a small static *bucket* set (``ctx_buckets`` /
  ``tgt_buckets`` / ``batch_buckets``) so the jitted shape family stays
  compile-cached — the scheduler never reorders (FIFO preserves
  arrival-order fairness and makes batch composition a pure function of
  the queue contents, which is what the determinism test pins).
  Newly-arrived requests join the next dispatch as soon as the current
  one retires — continuous batching degenerate to the one-shot case of
  a model whose whole decode is a single forward pass.
* **Prefix cache** — ``gpo_prefill`` output (per-layer context K/V) is
  cached under the request's ``prefix_key`` in an LRU of
  ``cache_entries`` entries. Repeated ICL prefixes across requests —
  the common serving shape: many queries conditioned on the same
  group's survey context — skip prefill entirely. The neural-process
  mask makes the context encoding exactly independent of targets, so a
  hit is *bit-equal* to the cold path (same cached arrays in, same
  jitted decode) and strictly cheaper: prefill is the O(M²) half.
* **int8 inference** — ``quantize_gpo_params`` rewrites the dense
  weights as ``QuantizedLinear`` leaves at load time (per-output-channel
  symmetric scales, the §10 contract) and ``core/gpo.py::_mm`` routes
  them through the fused int8 matmul kernel.
* **Token-text targets** — with an ``embedder`` (a frozen backbone's
  config and params), a request may carry its targets as token texts
  (``tgt_tokens``) in place of embeddings. ``step`` embeds the batch's
  texts on the device, packed several to a backbone row (first-fit
  decreasing, kept apart inside the row by segment ids; one program per
  row length and row bucket), as the masked mean pool of the backbone's
  final-normed hidden states, unit-normed, and places them into the
  decode input there: no embedding comes back to the host before the
  decode.

Everything timing-related is measurement only: scheduling decisions
depend exclusively on queue order, so a fixed arrival trace yields a
fixed batch composition on any machine.
"""
from __future__ import annotations

import functools
import time
from collections import OrderedDict, deque
from dataclasses import dataclass
from typing import Any, Hashable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import GPOConfig, ModelConfig, ServeConfig
from repro.core.gpo import GPOLayer, GPOPrefix, gpo_decode, gpo_prefill
from repro.data.embeddings import masked_mean, unit_norm
from repro.kernels import quantize_linear
from repro.models.transformer import has_segment_path, hidden_states
from repro.utils.spans import span

PyTree = Any

# GPOLayer fields that are dense matmul weights (quantized for int8
# serving); the ln1/ln2 RMS-norm scales stay f32.
_QUANT_FIELDS = ("wq", "wk", "wv", "wo", "w1", "w2")


def quantize_gpo_params(params: PyTree) -> PyTree:
    """Load-time int8 quantization of the GPO predictor's dense weights
    (DESIGN.md §12): ``in_proj``, ``head``, and every per-layer matmul
    become ``QuantizedLinear`` leaves (the stacked-layer leading axis is
    carried into per-layer scales); norm scales stay f32. The returned
    tree feeds every ``gpo_*`` entry point unchanged — ``_mm`` dispatches
    on the leaf type."""
    layers = params["layers"]
    qlayers = GPOLayer(**{
        f: (quantize_linear(getattr(layers, f)) if f in _QUANT_FIELDS
            else getattr(layers, f))
        for f in GPOLayer._fields})
    return {
        "in_proj": quantize_linear(params["in_proj"]),
        "layers": qlayers,
        "final_norm": params["final_norm"],
        "head": quantize_linear(params["head"]),
    }


# ---------------------------------------------------------------------------
# request / result / batch-record types
# ---------------------------------------------------------------------------
@dataclass
class Request:
    """One preference query: predict a group's answer distributions for
    ``tgt_x`` given the (ctx_x, ctx_y) in-context examples. In place of
    ``tgt_x`` the targets may be token texts, one per target point:
    ``tgt_tokens`` (t*A, max_len) int32, padded on the right, with their
    lengths ``tgt_lens`` (t*A,); the server's embedder embeds them.
    ``prefix_key`` identifies the shared context for prefix caching —
    two requests with the same key MUST carry identical (ctx_x, ctx_y);
    None disables caching for this request. ``arrival`` is seconds on
    the engine clock (load-generation metadata, not a scheduling
    input). ``deadline`` is an absolute engine-clock time past which the
    result is worthless to the caller (an RLHF scorer that already timed
    out): the scheduler drops the request instead of spending a decode
    slot on it, counted in ``ServeStats.expired``. None means no
    deadline."""

    rid: int
    ctx_x: np.ndarray  # (m*A, d_embed)
    ctx_y: np.ndarray  # (m*A,)
    tgt_x: Optional[np.ndarray] = None  # (t*A, d_embed)
    prefix_key: Optional[Hashable] = None
    arrival: float = 0.0
    deadline: Optional[float] = None  # absolute engine-clock seconds
    meta: Optional[dict] = None  # caller-owned (e.g. group/question ids)
    tgt_tokens: Optional[np.ndarray] = None  # (t*A, max_len) int32
    tgt_lens: Optional[np.ndarray] = None  # (t*A,)

    @property
    def num_targets(self) -> int:
        """Target points (t*A), embeddings or texts."""
        if self.tgt_x is not None:
            return self.tgt_x.shape[0]
        return len(self.tgt_lens)


@dataclass
class Completed:
    rid: int
    pred: np.ndarray  # (t, A) rows on the simplex
    cache_hit: bool
    arrival: float
    finished: float
    batch_index: int

    @property
    def latency(self) -> float:
        return self.finished - self.arrival


@dataclass(frozen=True)
class BatchRecord:
    """Composition of one decode dispatch — the deterministic-scheduler
    contract surface (tests pin these for a fixed arrival trace)."""

    rids: Tuple[int, ...]
    batch_pad: int  # padded batch size (a batch_buckets entry)
    ctx_bucket: int
    tgt_bucket: int
    hits: Tuple[bool, ...]
    # (ctx bucket, padded group size, contexts) per prefill dispatch
    prefills: Tuple[Tuple[int, int, int], ...] = ()


@dataclass
class ServeStats:
    submitted: int = 0
    rejected: int = 0
    completed: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    prefills: int = 0  # unique contexts actually prefilled
    evictions: int = 0
    expired: int = 0  # dropped unserved: deadline passed while queued


# ---------------------------------------------------------------------------
# jitted batch kernels (params passed positionally: jit caches per shape)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("cfg", "ctx_pad"))
def _prefill_batch(params, cfg: GPOConfig, ctx_pad: int, ctx_x, ctx_y,
                   ctx_len):
    """(B, M, d), (B, M), (B,) -> (k, v): two tuples of B per-row
    (L, ctx_pad, nh, hd) arrays. The work runs at the group's own
    bucket M; rows M..ctx_pad are exact zeros, so every cache entry has
    one shape whatever its bucket."""
    with jax.named_scope("prefill"):
        pre = jax.vmap(
            lambda cx, cy, cl: gpo_prefill(params, cfg, cx, cy, ctx_len=cl)
        )(ctx_x, ctx_y, ctx_len)
        widths = ((0, 0), (0, 0), (0, ctx_pad - ctx_x.shape[1]), (0, 0),
                  (0, 0))
        k, v = jnp.pad(pre.k, widths), jnp.pad(pre.v, widths)
        return tuple(k), tuple(v)


@functools.partial(jax.jit, static_argnames=("cfg", "num_options", "ctx_b"))
def _decode_batch(params, cfg: GPOConfig, num_options: int, ctx_b: int,
                  ks, vs, ctx_len, tgt_x):
    """B-tuples of cache entries (L, M_max, nh, hd), (B,), (B, T, d) ->
    (B, T/A, A) normalized preference rows (the ``predict_preferences``
    clip-and-normalize). The entries are stacked and cut to the batch's
    context bucket ``ctx_b`` here, inside the one program."""

    def one(k, v, cl, tx):
        mu, _ = gpo_decode(params, cfg, GPOPrefix(k=k, v=v), tx, ctx_len=cl)
        scores = jnp.clip(mu.reshape(-1, num_options), 1e-4, None)
        return scores / scores.sum(axis=-1, keepdims=True)

    with jax.named_scope("decode"):
        pk = jnp.stack(ks)[:, :, :ctx_b]
        pv = jnp.stack(vs)[:, :, :ctx_b]
        return jax.vmap(one)(pk, pv, ctx_len, tgt_x)


@functools.partial(jax.jit, static_argnames=("cfg", "per_row"))
def _embed_texts(params, cfg: ModelConfig, tokens, segment_ids, rows_total,
                 per_row: int):
    """(R, L) rows of token texts and their (R, L) segment ids: text k of
    a row (k = 1..``per_row``) is the row's tokens of segment k, 0 the
    unused tail -> (unit embeddings (R * per_row, d), pooled embeddings
    before the norm (R * per_row, d) f32, ``rows_total`` plus the
    (token, expert) pairs the backbone's dropless MoE handed to its
    grouped products). Pooled row ``r * per_row + k - 1`` is text k of
    row r: the float32 mean over its tokens, 0 for an empty segment.
    With one text a row the trunk takes no segment ids: the text is
    causal and the tail follows it."""
    with jax.named_scope("backbone"):
        hidden, _, rows = hidden_states(
            params, cfg, tokens=tokens,
            segment_ids=segment_ids if per_row > 1 else None)
    hidden = hidden.astype(jnp.float32)
    pooled = jnp.stack(
        [masked_mean(hidden, (segment_ids == k).astype(jnp.float32))
         for k in range(1, per_row + 1)], axis=1).reshape(-1, hidden.shape[-1])
    return unit_norm(pooled), pooled, rows_total + rows


@jax.jit
def _place_texts(tgt_x, emb, slots):
    """Write the rows of ``emb`` into the flat (batch * tgt) slots of
    ``tgt_x`` (B, T, d); slots past the end (padding rows) are dropped."""
    b, t, d = tgt_x.shape
    flat = tgt_x.reshape(b * t, d).at[slots].set(
        emb.astype(tgt_x.dtype), mode="drop")
    return flat.reshape(b, t, d)


def pack_rows(lengths: Sequence[int], row_len: int,
              per_row: int) -> List[List[int]]:
    """First-fit decreasing: the indices of ``lengths`` (each at most
    ``row_len``) in rows of at most ``row_len`` tokens and ``per_row``
    texts, longest first (ties in the order given), each into the first
    row it fits. A pure function of ``lengths``: batches replay."""
    rows: List[List[int]] = []
    used: List[int] = []
    for i in sorted(range(len(lengths)), key=lambda i: -lengths[i]):
        n = lengths[i]
        for r, row in enumerate(rows):
            if len(row) < per_row and used[r] + n <= row_len:
                row.append(i)
                used[r] += n
                break
        else:
            rows.append([i])
            used.append(n)
    return rows


def _bucket_of(n: int, buckets: Sequence[int], what: str) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{what} length {n} exceeds the largest bucket "
                     f"{buckets[-1]}; grow ServeConfig.{what}_buckets")


class PreferenceServer:
    """The multi-tenant serving engine (module docstring; DESIGN.md §12).

    ``submit`` enqueues (or rejects), ``step`` retires one fused batch,
    ``run_trace`` drives a full arrival trace open-loop and returns the
    completed results with per-request latencies.

    A prefix-cache entry is the K and V of one context, each
    (num_layers, ctx_buckets[-1], num_heads, head_dim) f32: computed at
    the context's own bucket and zero-padded to the largest by the
    prefill program, so it costs 2 x num_layers x ctx_buckets[-1] x
    d_model x 4 bytes whatever its length (655 KB at 4 layers, d_model
    128 and a largest bucket of 160). ``_decode_batch`` gathers the
    batch's entries and cuts them to its context bucket on the device.
    """

    def __init__(self, params: PyTree, gpo_cfg: GPOConfig,
                 serve_cfg: ServeConfig = ServeConfig(), *,
                 num_options: int,
                 embedder: Optional[Tuple[ModelConfig, PyTree]] = None):
        serve_cfg.validate()
        if embedder is not None and embedder[0].d_model != gpo_cfg.d_embed:
            raise ValueError(
                f"the embedder's width {embedder[0].d_model} is not the "
                f"predictor's d_embed {gpo_cfg.d_embed}")
        self.embedder = embedder
        for b in serve_cfg.tgt_buckets:
            if b % num_options:
                raise ValueError(
                    f"tgt bucket {b} is not a multiple of "
                    f"num_options={num_options}: padded target rows must "
                    "reshape into whole questions")
        self.gcfg = gpo_cfg
        self.scfg = serve_cfg
        self.num_options = num_options
        self.params = (quantize_gpo_params(params)
                       if serve_cfg.int8_weights else params)
        self._queue: deque[Request] = deque()
        # prefix_key -> (k (L, M_max, nh, hd), v, ctx_len): computed at
        # the request's own ctx bucket, zero-padded to the largest one
        self._cache: OrderedDict[Hashable, tuple] = OrderedDict()
        # K and V of a partial batch's padding rows
        self._zero_entry = jnp.zeros(
            (gpo_cfg.num_layers, serve_cfg.ctx_buckets[-1],
             gpo_cfg.num_heads, gpo_cfg.head_dim), jnp.float32)
        self.batches: List[BatchRecord] = []
        self.stats = ServeStats()
        # (token, expert) pairs the embedder's grouped expert products
        # were handed, summed on the device
        self.expert_rows = jnp.zeros((), jnp.int32)
        self._clock_start = time.perf_counter()

    # -- clock ----------------------------------------------------------
    def now(self) -> float:
        return time.perf_counter() - self._clock_start

    def reset(self, *, clear_cache: bool = True) -> None:
        """Drop queued work, stats, and the batch log (and optionally the
        prefix cache) — between benchmark phases."""
        self._queue.clear()
        self.batches = []
        self.stats = ServeStats()
        self.expert_rows = jnp.zeros((), jnp.int32)
        if clear_cache:
            self._cache.clear()
        self._clock_start = time.perf_counter()

    # -- admission ------------------------------------------------------
    def submit(self, req: Request) -> bool:
        if (req.tgt_x is None) == (req.tgt_tokens is None):
            raise ValueError("a request carries tgt_x or tgt_tokens, "
                             "exactly one of them")
        if req.tgt_tokens is not None and self.embedder is None:
            raise ValueError("token texts need a server built with an "
                             "embedder")
        self.stats.submitted += 1
        if self.scfg.max_queue and len(self._queue) >= self.scfg.max_queue:
            self.stats.rejected += 1
            return False
        self._queue.append(req)
        return True

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    # -- prefix cache ---------------------------------------------------
    def _cache_get(self, key: Hashable):
        if key is None or self.scfg.cache_entries == 0:
            return None
        entry = self._cache.get(key)
        if entry is not None:
            self._cache.move_to_end(key)
        return entry

    def _cache_put(self, key: Hashable, entry) -> None:
        if key is None or self.scfg.cache_entries == 0:
            return
        self._cache[key] = entry
        self._cache.move_to_end(key)
        while len(self._cache) > self.scfg.cache_entries:
            self._cache.popitem(last=False)
            self.stats.evictions += 1

    # -- one engine step ------------------------------------------------
    def step(self) -> List[Completed]:
        """Retire one fused batch: pop up to ``max_batch`` head-of-line
        requests, prefill the cache misses (batched, at each request's
        own ctx bucket so cache entries are batch-composition-independent
        and hits stay bit-equal), gather everyone's prefix K/V, decode
        once, complete. Requests whose ``deadline`` already passed are
        dropped during batch assembly wherever they sit in the queue —
        not just at the head — (counted ``expired``, never decoded),
        while live requests keep strict FIFO order (the no-reorder
        determinism contract): under overload this sheds exactly the
        work nobody is waiting for instead of letting it consume batch
        slots or return results after their deadline.

        Each phase is a host span (``utils/spans.py``) under
        ``serve.step``: ``serve.admit``, one ``serve.prefill`` per
        context-bucket group, ``serve.gather``, ``serve.decode`` (the
        dispatch alone), ``serve.wait`` and ``serve.complete``. Prefill
        and decode count their real rows (``rows``) and the padded rows
        they compute (``computed``)."""
        with span("serve.step", batch=len(self.batches)) as step_span:
            with span("serve.admit"):
                batch = self._admit()
            step_span.set(requests=len(batch[0]) if batch else 0)
            if batch is None:
                return []
            reqs, ctx_b, tgt_b, batch_b, hits, entries, by_bucket = batch

            # batched prefill of the misses, grouped by own ctx bucket
            fresh: dict = {}
            prefills = []
            for b, group in sorted(by_bucket.items()):
                gb = _bucket_of(len(group), self.scfg.batch_buckets, "batch")
                lens = np.array([r.ctx_x.shape[0] for r in group], np.int32)
                with span("serve.prefill", contexts=len(group),
                          rows=int(lens.sum()), computed=gb * b):
                    cxs = np.zeros((gb, b, group[0].ctx_x.shape[1]),
                                   np.float32)
                    cys = np.zeros((gb, b), np.float32)
                    for i, r in enumerate(group):
                        cxs[i, :lens[i]] = r.ctx_x
                        cys[i, :lens[i]] = r.ctx_y
                    pk, pv = _prefill_batch(
                        self.params, self.gcfg, self.scfg.ctx_buckets[-1],
                        jnp.asarray(cxs), jnp.asarray(cys),
                        jnp.asarray(np.pad(lens, (0, gb - len(group)))))
                    self.stats.prefills += len(group)
                    for i, r in enumerate(group):
                        entry = (pk[i], pv[i], int(lens[i]))
                        fresh[r.prefix_key] = entry
                        self._cache_put(r.prefix_key, entry)
                        if r.prefix_key is None:
                            entries[id(r)] = entry
                prefills.append((b, gb, len(group)))

            # embed the token-text targets (dispatched before the host
            # packs the rest, so the device starts on them first)
            texts = [(i * tgt_b + j, r.tgt_tokens[j], n)
                     for i, r in enumerate(reqs) if r.tgt_tokens is not None
                     for j, n in enumerate(r.tgt_lens)]
            placed = [(unit, slots) for unit, _, slots in self._embed_groups(
                texts, batch_b * tgt_b)]

            # gather the entries and pack the targets; the decode
            # program stacks and cuts the entries to ctx_b itself
            with span("serve.gather"):
                ks = [self._zero_entry] * batch_b
                vs = list(ks)
                lens = np.zeros(batch_b, np.int32)
                txs = np.zeros((batch_b, tgt_b, self.gcfg.d_embed),
                               np.float32)
                for i, r in enumerate(reqs):
                    ks[i], vs[i], lens[i] = (entries.get(id(r))
                                             or fresh[r.prefix_key])
                    if r.tgt_x is not None:
                        txs[i, :r.tgt_x.shape[0]] = r.tgt_x
                if placed and all(r.tgt_x is None for r in reqs):
                    ctx_len = jax.device_put(lens)
                    tgt_x = jnp.zeros(txs.shape, txs.dtype)
                else:
                    ctx_len, tgt_x = jax.device_put((lens, txs))
                for unit, slots in placed:
                    tgt_x = _place_texts(tgt_x, unit, slots)
            with span("serve.decode",
                      rows=sum(r.num_targets for r in reqs),
                      computed=batch_b * tgt_b):
                preds = _decode_batch(self.params, self.gcfg,
                                      self.num_options, ctx_b, tuple(ks),
                                      tuple(vs), ctx_len, tgt_x)
            with span("serve.wait"):
                preds = np.asarray(jax.block_until_ready(preds))

            with span("serve.complete"):
                finished = self.now()
                batch_index = len(self.batches)
                self.batches.append(BatchRecord(
                    rids=tuple(r.rid for r in reqs), batch_pad=batch_b,
                    ctx_bucket=ctx_b, tgt_bucket=tgt_b, hits=tuple(hits),
                    prefills=tuple(prefills)))
                out = []
                for i, r in enumerate(reqs):
                    rows = r.num_targets // self.num_options
                    out.append(Completed(
                        rid=r.rid, pred=preds[i, :rows], cache_hit=hits[i],
                        arrival=r.arrival, finished=finished,
                        batch_index=batch_index))
                    self.stats.completed += 1
            return out

    def _text_rows(self, texts):
        """The rows of ``texts`` (slot, tokens, length), as (row length,
        texts a row, rows of indices into ``texts``) per row length.
        Where the embedder has a segment path (``has_segment_path``),
        every text shares one row length, the smallest ``text_buckets``
        entry that holds the longest, and rows of up to ``row length //
        text_buckets[0]`` texts (``pack_rows``); otherwise each text has
        a row of its own length bucket."""
        cfg, sc = self.embedder[0], self.scfg
        lengths = [n for _, _, n in texts]
        if has_segment_path(cfg):
            row_len = _bucket_of(max(lengths), sc.text_buckets, "text")
            per_row = row_len // sc.text_buckets[0]
            return [(row_len, per_row, pack_rows(lengths, row_len, per_row))]
        by_len: dict = {}
        for i, n in enumerate(lengths):
            by_len.setdefault(_bucket_of(n, sc.text_buckets, "text"),
                              []).append([i])
        return [(row_len, 1, rows) for row_len, rows in sorted(by_len.items())]

    def _embed_groups(self, texts, n_slots: int):
        """Run ``texts`` (slot, tokens, length) through the embedder in
        the rows of ``_text_rows``, at most ``text_row_buckets[-1]`` rows
        a call, padded to a row bucket. Yields (unit embeddings, pooled
        embeddings before the norm, slots) per call, all on the device,
        one entry per (row, text place); empty places get slot
        ``n_slots``. Each call is a ``serve.embed`` span counting its
        ``texts``, real ``tokens``, ``rows`` and ``computed`` tokens
        (row bucket x row length)."""
        if not texts:
            return
        if self.embedder is None:
            raise ValueError("token texts need a server built with an "
                             "embedder")
        cfg, params = self.embedder
        most = self.scfg.text_row_buckets[-1]
        for row_len, per_row, all_rows in self._text_rows(texts):
            for start in range(0, len(all_rows), most):
                rows = all_rows[start:start + most]
                rb = _bucket_of(len(rows), self.scfg.text_row_buckets,
                                "text_row")
                with span("serve.embed", texts=sum(map(len, rows)),
                          tokens=sum(texts[i][2] for row in rows
                                     for i in row),
                          rows=len(rows), computed=rb * row_len):
                    toks = np.zeros((rb, row_len), np.int32)
                    segs = np.zeros((rb, row_len), np.int32)
                    slots = np.full(rb * per_row, n_slots, np.int32)
                    for r, row in enumerate(rows):
                        at = 0
                        for k, i in enumerate(row):
                            slot, tok, n = texts[i]
                            toks[r, at:at + n] = tok[:n]
                            segs[r, at:at + n] = k + 1
                            slots[r * per_row + k] = slot
                            at += n
                    toks, segs, slots = jax.device_put((toks, segs, slots))
                    unit, pooled, self.expert_rows = _embed_texts(
                        params, cfg, toks, segs, self.expert_rows, per_row)
                yield unit, pooled, slots

    def embed(self, tokens: Sequence[np.ndarray]):
        """Embed token texts with the programs ``step`` uses: returns
        (unit embeddings, pooled embeddings before the norm), each (n, d)
        float32 on the host, in the order given."""
        texts = [(i, t, len(t)) for i, t in enumerate(tokens)]
        d = self.gcfg.d_embed
        unit = np.zeros((len(texts), d), np.float32)
        pooled = np.zeros((len(texts), d), np.float32)
        for u, p, slots in self._embed_groups(texts, len(texts)):
            real = np.asarray(slots) < len(texts)
            idx = np.asarray(slots)[real]
            unit[idx] = np.asarray(u)[real]
            pooled[idx] = np.asarray(p)[real]
        return unit, pooled

    def _admit(self):
        """Pop up to ``max_batch`` live head-of-line requests (dropping
        those whose deadline has passed), pick the batch's buckets, look
        up the cache, and group the misses to prefill by their own ctx
        bucket (a miss key shared within the batch prefills once).
        Returns (requests, ctx bucket, tgt bucket, batch bucket, hit
        flags, cached entries by ``id(request)``, misses by bucket), or
        None when no live request is queued."""
        now = self.now()
        reqs: List[Request] = []
        while self._queue and len(reqs) < self.scfg.max_batch:
            r = self._queue.popleft()
            if r.deadline is not None and now >= r.deadline:
                self.stats.expired += 1
                continue
            reqs.append(r)
        if not reqs:
            return None
        ctx_b = _bucket_of(max(r.ctx_x.shape[0] for r in reqs),
                           self.scfg.ctx_buckets, "ctx")
        tgt_b = _bucket_of(max(r.num_targets for r in reqs),
                           self.scfg.tgt_buckets, "tgt")
        batch_b = _bucket_of(len(reqs), self.scfg.batch_buckets, "batch")
        entries: dict = {}
        hits: List[bool] = []
        misses: List[Request] = []
        seen_miss_keys: set = set()
        for r in reqs:
            entry = self._cache_get(r.prefix_key)
            if entry is not None:
                hits.append(True)
                entries[id(r)] = entry
                self.stats.cache_hits += 1
            else:
                hits.append(False)
                self.stats.cache_misses += 1
                if r.prefix_key is None or r.prefix_key not in seen_miss_keys:
                    misses.append(r)
                    if r.prefix_key is not None:
                        seen_miss_keys.add(r.prefix_key)
        by_bucket: dict[int, List[Request]] = {}
        for r in misses:
            b = _bucket_of(r.ctx_x.shape[0], self.scfg.ctx_buckets, "ctx")
            by_bucket.setdefault(b, []).append(r)
        return reqs, ctx_b, tgt_b, batch_b, hits, entries, by_bucket

    # -- open-loop trace driver ----------------------------------------
    def run_trace(self, requests: Sequence[Request],
                  *, reset: bool = True,
                  clear_cache: bool = False) -> List[Completed]:
        """Drive a full arrival trace: requests are admitted when the
        engine clock passes their ``arrival`` (open loop — a slow engine
        builds queue depth and, past ``max_queue``, rejections), and the
        engine steps whenever work is queued. Returns completions in
        retirement order; rejected rids are in ``stats.rejected``."""
        if reset:
            self.reset(clear_cache=clear_cache)
        trace = sorted(requests, key=lambda r: r.arrival)
        results: List[Completed] = []
        i = 0
        while i < len(trace) or self._queue:
            now = self.now()
            while i < len(trace) and trace[i].arrival <= now:
                self.submit(trace[i])
                i += 1
            if not self._queue:
                if i >= len(trace):
                    break
                time.sleep(min(5e-4, max(0.0, trace[i].arrival - now)))
                continue
            results.extend(self.step())
        return results


# ---------------------------------------------------------------------------
# synthetic load generation + latency summaries (shared by the serve CLI
# and the tests)
# ---------------------------------------------------------------------------
def make_request_trace(data, groups, *, num_requests: int,
                       hit_ratio: float = 0.0,
                       num_context: Tuple[int, int] = (6, 16),
                       num_target: Tuple[int, int] = (2, 8),
                       rate: Optional[float] = None,
                       seed: int = 0) -> List[Request]:
    """Build a request trace against a ``SurveyData`` population.

    ``hit_ratio`` controls prefix-cache pressure: the trace draws
    ``ceil((1 - hit_ratio) * N)`` unique (group, context) prefixes and
    spreads the remaining requests across them (fresh targets each), so
    the realized steady-state hit rate is ``hit_ratio`` regardless of
    arrival order. ``num_context``/``num_target`` are inclusive ranges
    of QUESTIONS (points are questions x num_options) sampled per
    prefix / per request — the ragged-length workload the bucketed
    batcher exists for. ``rate`` (requests/sec) spaces arrivals
    uniformly; None means all arrive at t=0 (saturation)."""
    rng = np.random.default_rng(seed)
    phi = np.asarray(data.phi)
    prefs = np.asarray(data.prefs)
    mask = np.asarray(data.mask)
    a = data.num_options
    d = phi.shape[-1]

    n_unique = max(1, int(np.ceil((1.0 - hit_ratio) * num_requests)))
    prefixes = []
    for u in range(n_unique):
        g = int(groups[rng.integers(len(groups))])
        answered = np.flatnonzero(mask[g])
        m = int(rng.integers(num_context[0], num_context[1] + 1))
        m = min(m, max(1, len(answered) - num_target[1]))
        ctx_q = rng.choice(answered, size=m, replace=False)
        ctx_x = phi[ctx_q].reshape(-1, d)
        ctx_y = prefs[g, ctx_q].reshape(-1)
        rest = np.setdiff1d(answered, ctx_q)
        prefixes.append((g, ctx_x, ctx_y, rest, u))

    assign = np.concatenate([
        np.arange(n_unique),
        rng.integers(0, n_unique, size=num_requests - n_unique)])
    rng.shuffle(assign)
    out = []
    for rid in range(num_requests):
        g, ctx_x, ctx_y, rest, u = prefixes[int(assign[rid])]
        t = int(rng.integers(num_target[0], num_target[1] + 1))
        tgt_q = rng.choice(rest, size=min(t, len(rest)), replace=False)
        tgt_x = phi[tgt_q].reshape(-1, d)
        arrival = 0.0 if rate is None else rid / rate
        out.append(Request(
            rid=rid, ctx_x=ctx_x.astype(np.float32),
            ctx_y=ctx_y.astype(np.float32),
            tgt_x=tgt_x.astype(np.float32),
            prefix_key=("ctx", g, u), arrival=arrival,
            meta={"group": g, "tgt_q": tgt_q}))
    return out


def latency_summary(results: Sequence[Completed],
                    wall_seconds: float) -> dict:
    """p50/p99 latency (ms) + throughput over a completed trace."""
    if not results:
        return {"completed": 0}
    lat = np.asarray([r.latency for r in results]) * 1e3
    return {
        "completed": len(results),
        "p50_ms": float(np.percentile(lat, 50)),
        "p95_ms": float(np.percentile(lat, 95)),
        "p99_ms": float(np.percentile(lat, 99)),
        "mean_ms": float(lat.mean()),
        "max_ms": float(lat.max()),
        "qps": float(len(results) / max(wall_seconds, 1e-9)),
        "hit_rate": float(np.mean([r.cache_hit for r in results])),
    }
