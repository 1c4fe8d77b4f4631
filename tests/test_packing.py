"""Texts packed into shared backbone rows: the segment masks of the
Mamba-2 scan, the causal conv and attention (each segment computes as if
it ran alone), the first-fit-decreasing packer, and the serving engine's
packed embed calls against one text a row."""
import contextlib
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ATTN, MAMBA, GPOConfig, ServeConfig, get_arch
from repro.configs import smoke_variant
from repro.core import PreferenceServer, init_gpo_params
from repro.core.serving import _embed_texts, pack_rows
from repro.models import attention as A
from repro.models import hidden_states, init_params
from repro.models.ssm import causal_depthwise_conv, ssd_chunked
from repro.models.transformer import has_segment_path
from repro.utils import spans

# float32 throughout: a packed segment and the same text alone differ
# by the rounding of differently chunked sums alone
TOL = 1e-5


def _segments(lengths):
    """(1, sum) ids 1..K of contiguous segments of the given lengths."""
    return jnp.asarray(np.repeat(np.arange(1, len(lengths) + 1),
                                 lengths)[None], jnp.int32)


def _ssd_inputs(s, seed=0, h=2, p=8, n=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (1, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, s, h)))
    a_log = jax.random.normal(ks[2], (h,)) * 0.5
    b = jax.random.normal(ks[3], (1, s, n)) * 0.5
    c = jax.random.normal(ks[4], (1, s, n)) * 0.5
    return x, dt, a_log, b, c, jnp.ones((h,))


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.max(np.abs(got - want)) <= tol * max(np.max(np.abs(want)), 1)


# ---------------------------------------------------------------------------
# the mixers' segment masks
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("lengths", [
    [5, 14, 13],    # boundaries inside chunks of 8
    [8, 16, 8],     # boundaries on chunk edges
    [7, 1, 1, 23],  # one-token segments
    [3, 26, 3],     # a segment across several chunks
    [11, 19],       # a row that is no multiple of the chunk
], ids=["inside", "edge", "one-token", "across", "ragged"])
def test_ssd_segments_equal_each_segment_alone(lengths):
    x, dt, a_log, b, c, d = _ssd_inputs(sum(lengths))
    y, state = ssd_chunked(x, dt, a_log, b, c, d, 8,
                           segment_ids=_segments(lengths))
    at = 0
    for n in lengths:
        part = slice(at, at + n)
        alone, alone_state = ssd_chunked(x[:, part], dt[:, part], a_log,
                                         b[:, part], c[:, part], d, 8)
        _close(y[:, part], alone)
        at += n
    _close(state, alone_state)  # the final state is the last segment's


@pytest.mark.parametrize("width", [2, 4])
def test_conv_segments_leak_nothing(width):
    lengths = [3, 1, 6, 2]
    ks = jax.random.split(jax.random.PRNGKey(width), 3)
    x = jax.random.normal(ks[0], (1, sum(lengths), 5))
    w = jax.random.normal(ks[1], (width, 5))
    bias = jax.random.normal(ks[2], (5,))
    seg = _segments(lengths)
    y, _ = causal_depthwise_conv(x, w, bias, segment_ids=seg)
    at = 0
    for n in lengths:
        alone, _ = causal_depthwise_conv(x[:, at:at + n], w, bias)
        np.testing.assert_allclose(np.asarray(y[:, at:at + n]),
                                   np.asarray(alone), rtol=1e-6, atol=1e-6)
        at += n
    # a change to the first segment reaches no later one
    y2, _ = causal_depthwise_conv(x.at[:, :3].set(9.0), w, bias,
                                  segment_ids=seg)
    np.testing.assert_array_equal(np.asarray(y2[:, 3:]), np.asarray(y[:, 3:]))


def _attn_cfg(rope: bool):
    cfg = smoke_variant(get_arch("qwen2-0.5b"))
    return cfg if rope else replace(cfg, use_rope=False, attn_scale=0.05)


@pytest.mark.parametrize("rope", [False, True], ids=["nope", "rope"])
def test_attend_full_segments_equal_each_text_alone(rope):
    cfg = _attn_cfg(rope)
    p = A.init_attn_params(jax.random.PRNGKey(1), cfg, jnp.float32)
    lengths = [4, 9, 1, 6]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, sum(lengths),
                                                  cfg.d_model))
    window, theta = jnp.asarray(0), jnp.asarray(cfg.rope_theta, jnp.float32)
    seg = _segments(lengths)
    np.testing.assert_array_equal(
        np.asarray(A.segment_positions(seg))[0],
        np.concatenate([np.arange(n) for n in lengths]))
    y = A.attend_full(p, x, cfg, window=window, theta=theta, segment_ids=seg)
    at = 0
    for n in lengths:
        alone = A.attend_full(p, x[:, at:at + n], cfg, window=window,
                              theta=theta)
        _close(y[:, at:at + n], alone)
        at += n


def test_chunked_attention_segments_match_dense():
    """The q-chunked path masks segments as the dense one does."""
    cfg = _attn_cfg(True)
    p = A.init_attn_params(jax.random.PRNGKey(3), cfg, jnp.float32)
    s = 64
    x = jax.random.normal(jax.random.PRNGKey(4), (1, s, cfg.d_model))
    q, k, v = A._project_qkv(p, x, cfg.num_heads, cfg.num_kv_heads,
                             cfg.head_dim, cfg.norm_eps)
    seg = _segments([10, 30, 1, 23])
    mask = (A.make_causal_window_mask(jnp.arange(s)[None], jnp.arange(s)[None],
                                      0)
            & (seg[:, :, None] == seg[:, None, :]))
    dense = A.gqa_scores_softmax(q, k, v, mask, None)
    chunked = A._chunked_gqa(q, k, v, jnp.asarray(0), None, q_chunk=16,
                             segment_ids=seg)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(chunked),
                               rtol=1e-5, atol=1e-6)


# one segment covering each row multiplies by exact ones: bit-equal to
# the call without segment ids, which runs the program it always ran
def _ssd_call(seg):
    x, dt, a_log, b, c, d = _ssd_inputs(24)
    return ssd_chunked(x, dt, a_log, b, c, d, 8, segment_ids=seg)


def _conv_call(seg):
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 6))
    w = jax.random.normal(jax.random.PRNGKey(6), (4, 6))
    return causal_depthwise_conv(x, w, jnp.ones((6,)), segment_ids=seg)


def _attn_call(seg):
    cfg = _attn_cfg(True)
    p = A.init_attn_params(jax.random.PRNGKey(1), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, cfg.d_model))
    return A.attend_full(p, x, cfg, window=jnp.asarray(0),
                         theta=jnp.asarray(cfg.rope_theta, jnp.float32),
                         segment_ids=seg)


@pytest.mark.parametrize("call", [_ssd_call, _conv_call, _attn_call],
                         ids=["ssd", "conv", "attention"])
def test_one_segment_a_row_is_bit_equal_to_no_segment_ids(call):
    plain = call(None)
    one = call(jnp.ones((1, 24), jnp.int32))
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(one)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hidden_states_without_segment_ids_trace_no_mask():
    """No segment ids: the trunk traces none of the segment path (no
    position restart, no mask); one segment a row computes the same
    numbers up to the fusion's rounding."""
    cfg = _mixed_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (1, 24), 0,
                                cfg.vocab_size)

    def run(seg):
        return hidden_states(params, cfg, tokens=tokens, segment_ids=seg)

    plain = str(jax.make_jaxpr(lambda: hidden_states(params, cfg,
                                                     tokens=tokens))())
    assert str(jax.make_jaxpr(lambda: run(None))()) == plain
    assert "cummax" not in plain
    assert "cummax" in str(jax.make_jaxpr(
        lambda: run(jnp.ones((1, 24), jnp.int32)))())
    _close(run(jnp.ones((1, 24), jnp.int32))[0], run(None)[0])


# ---------------------------------------------------------------------------
# packed rows through the whole backbone
# ---------------------------------------------------------------------------
def _mixed_cfg():
    return replace(
        get_arch("granite-4.0-h-small"), num_layers=3,
        block_pattern=(MAMBA, ATTN, MAMBA), d_model=64, vocab_size=256,
        num_heads=4, num_kv_heads=2, head_dim=16, attn_scale=0.0625,
        d_ff=32, num_experts=8, experts_per_token=3, experts_held=4,
        shared_expert_ff=48, ssm_state_size=16, ssm_head_dim=16,
        ssm_chunk=8, param_dtype="float32", activation_dtype="float32")


FAMILIES = {
    "mixed": _mixed_cfg,
    "mamba": lambda: smoke_variant(get_arch("mamba2-780m")),
    "attention": lambda: smoke_variant(get_arch("qwen2-0.5b")),
}


def _rows(lengths_per_row, row_len, vocab, seed=0):
    """Token rows packed with texts of the given lengths, their segment
    ids, and the texts."""
    rng = np.random.default_rng(seed)
    toks = np.zeros((len(lengths_per_row), row_len), np.int32)
    segs = np.zeros_like(toks)
    texts = []
    for r, lengths in enumerate(lengths_per_row):
        at = 0
        for k, n in enumerate(lengths):
            t = rng.integers(0, vocab, n).astype(np.int32)
            toks[r, at:at + n], segs[r, at:at + n] = t, k + 1
            texts.append((r, k, t))
            at += n
    return toks, segs, texts


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_packed_embeddings_match_one_text_a_row(family):
    cfg = FAMILIES[family]()
    assert has_segment_path(cfg)
    params = init_params(cfg, jax.random.PRNGKey(0))
    per_row, row_len = 3, 32
    # an empty row, a full row, a one-token text and a ragged tail
    toks, segs, texts = _rows([[9, 1, 17], [32], [], [5, 12]], row_len,
                              cfg.vocab_size)
    zero = jnp.zeros((), jnp.int32)
    unit, pooled, rows = _embed_texts(params, cfg, toks, segs, zero,
                                      per_row)
    pooled = np.asarray(pooled)
    assert pooled.shape == (4 * per_row, cfg.d_model)
    filled = set()
    for r, k, t in texts:
        one = np.zeros((1, row_len), np.int32)
        one[0, :len(t)] = t
        seg = (np.arange(row_len) < len(t)).astype(np.int32)[None]
        _, want, _ = _embed_texts(params, cfg, one, seg, zero, 1)
        _close(pooled[r * per_row + k], want[0], 1e-4)
        filled.add(r * per_row + k)
    empty = [i for i in range(4 * per_row) if i not in filled]
    assert not np.any(pooled[empty])  # empty places pool to 0
    np.testing.assert_allclose(np.linalg.norm(np.asarray(unit)[sorted(filled)],
                                              axis=-1), 1.0, rtol=1e-5)


@pytest.mark.parametrize("arch", ["zamba2-1.2b", "whisper-small"])
def test_families_without_a_segment_path_refuse_segment_ids(arch):
    cfg = smoke_variant(get_arch(arch))
    assert not has_segment_path(cfg)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 8), jnp.int32)
    kw = ({"enc_embeds": jnp.zeros((1, cfg.enc_seq_len, cfg.d_model))}
          if cfg.is_encoder_decoder else {})
    with pytest.raises(ValueError, match="segment"):
        hidden_states(params, cfg, tokens=tokens,
                      segment_ids=jnp.ones((1, 8), jnp.int32), **kw)


# ---------------------------------------------------------------------------
# the packer
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", range(4))
def test_pack_rows_places_every_text_once_within_bounds(seed):
    rng = np.random.default_rng(seed)
    lengths = [int(n) for n in rng.integers(1, 129, 60)]
    row_len, per_row = 128, 4
    rows = pack_rows(lengths, row_len, per_row)
    assert sorted(i for row in rows for i in row) == list(range(60))
    for row in rows:
        assert len(row) <= per_row
        assert sum(lengths[i] for i in row) <= row_len
    assert pack_rows(lengths, row_len, per_row) == rows  # replays
    # first fit decreasing: no text would have fitted in an earlier row
    for r, row in enumerate(rows):
        for i in row:
            for earlier in rows[:r]:
                before = [j for j in earlier
                          if (-lengths[j], j) < (-lengths[i], i)]
                assert (len(before) >= per_row or sum(
                    lengths[j] for j in before) + lengths[i] > row_len)


def test_pack_rows_fills_the_cell_pool_as_counted():
    # 4 texts of 128 share a 512 row; 512 takes a row alone
    assert pack_rows([128] * 4 + [512], 512, 4) == [[4], [0, 1, 2, 3]]
    # at most per_row texts, however short
    assert [len(r) for r in pack_rows([10] * 9, 512, 4)] == [4, 4, 1]


# ---------------------------------------------------------------------------
# the serving engine's embed calls
# ---------------------------------------------------------------------------
def _server(cfg, params, scfg):
    gpo = GPOConfig(d_embed=cfg.d_model, d_model=32, num_layers=2,
                    num_heads=4, d_ff=64)
    return PreferenceServer(init_gpo_params(gpo, jax.random.PRNGKey(0)), gpo,
                            scfg, num_options=5, embedder=(cfg, params))


SCFG = ServeConfig(max_batch=4, batch_buckets=(1, 2, 4), ctx_buckets=(20,),
                   tgt_buckets=(5, 10), cache_entries=8,
                   text_buckets=(8, 16, 32), text_row_buckets=(2, 4))


@pytest.fixture(scope="module")
def mixed():
    cfg = _mixed_cfg()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@contextlib.contextmanager
def _profiled(trace_dir):
    spans.clear()
    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def test_embed_groups_pack_count_and_map_back(mixed, tmp_path):
    cfg, params = mixed
    srv = _server(cfg, params, SCFG)
    rng = np.random.default_rng(3)
    lengths = [30, 3, 17, 9, 12, 1, 25, 8, 6, 20, 2]
    texts = [(10 + i, rng.integers(0, cfg.vocab_size, 32).astype(np.int32),
              n) for i, n in enumerate(lengths)]
    n_slots = 40
    list(srv._embed_groups(texts, n_slots))  # compile outside the capture
    with _profiled(tmp_path):
        calls = list(srv._embed_groups(texts, n_slots))
    # rows of 32 tokens, 32 // 8 = 4 texts a row, in calls of <= 4 rows
    rows = pack_rows(lengths, 32, 4)
    assert len(rows) == 5  # 133 tokens: the fewest rows of 32
    embeds = [s for s in spans.recorded() if s.name == "serve.embed"]
    # 4 rows, then 1 padded to the row bucket of 2
    assert [(s.counts["rows"], s.counts["computed"]) for s in embeds] == [
        (4, 4 * 32), (1, 2 * 32)]
    assert [s.counts["texts"] for s in embeds] == [
        sum(map(len, rows[:4])), len(rows[4])]
    assert sum(s.counts["tokens"] for s in embeds) == sum(lengths)
    slots = np.concatenate([np.asarray(sl) for _, _, sl in calls])
    assert sorted(slots[slots < n_slots]) == [10 + i for i in range(11)]
    assert np.sum(slots == n_slots) == (4 + 2) * 4 - 11
    # each slot's embedding is its own text's
    pooled = np.concatenate([np.asarray(p) for _, p, _ in calls])
    for slot, tok, n in texts:
        _, alone = srv.embed([tok[:n]])
        _close(pooled[list(slots).index(slot)], alone[0], 1e-4)


def test_embed_returns_texts_in_input_order(mixed):
    cfg, params = mixed
    srv = _server(cfg, params, SCFG)
    rng = np.random.default_rng(4)
    texts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
             for n in (5, 31, 2, 16, 9, 27, 1)]
    unit, pooled = srv.embed(texts)
    for i, t in enumerate(texts):
        u1, p1 = srv.embed([t])
        _close(pooled[i], p1[0], 1e-4)
        _close(unit[i], u1[0], 1e-4)
    # short texts run in short rows
    assert srv._text_rows([(0, t, len(t)) for t in texts[:1]])[0][:2] == (8, 1)


def test_no_segment_path_keeps_one_text_a_row():
    cfg = smoke_variant(get_arch("zamba2-1.2b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    srv = _server(cfg, params, SCFG)
    texts = [(i, np.arange(n, dtype=np.int32), n)
             for i, n in enumerate((3, 20, 7, 30))]
    assert srv._text_rows(texts) == [(8, 1, [[0], [2]]), (32, 1, [[1], [3]])]
    unit, pooled = srv.embed([t[:n] for _, t, n in texts])
    for i, (_, t, n) in enumerate(texts):
        _close(pooled[i], srv.embed([t[:n]])[1][0], 1e-5)
