"""Serving-engine invariants (DESIGN.md §12): prefix-split exactness,
ragged-batch equivalence, cache hit==miss numerics, int8 tolerance,
deterministic scheduling, admission, and the checkpoint restore contract.
"""
import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import (
    restore_checkpoint,
    restore_checkpoint_quantized,
    save_checkpoint,
)
from repro.configs import FedConfig, GPOConfig, ServeConfig
from repro.core import (
    FederatedGPO,
    GPOPrefix,
    PreferenceServer,
    Request,
    gpo_apply,
    gpo_decode,
    gpo_prefill,
    init_gpo_params,
    make_request_trace,
    predict_preferences,
    quantize_gpo_params,
)
from repro.data import SurveyConfig, make_survey_data, split_groups
from repro.kernels import (
    QuantizedLinear,
    dequantize_linear,
    int8_matmul,
    quantize_linear,
)
from repro.kernels.ref import ref_int8_matmul

CFG = GPOConfig(d_embed=16, d_model=32, num_layers=2, num_heads=4, d_ff=64)
SCFG = ServeConfig(max_batch=4, batch_buckets=(1, 2, 4),
                   ctx_buckets=(20, 40), tgt_buckets=(10, 20),
                   cache_entries=16)
# three context buckets: entries own 40 or 80 rows, stored at 160
WIDE = ServeConfig(max_batch=4, batch_buckets=(1, 2, 4),
                   ctx_buckets=(40, 80, 160), tgt_buckets=(10, 20),
                   cache_entries=16)


def _params(key=0, scale=1.0):
    p = init_gpo_params(CFG, jax.random.PRNGKey(key))
    return jax.tree.map(lambda a: a * scale, p) if scale != 1.0 else p


def _icl(key, m=6, t=10):
    kx, ky, kt = jax.random.split(jax.random.PRNGKey(key), 3)
    ctx_x = jax.random.normal(kx, (m, CFG.d_embed))
    ctx_y = jax.random.uniform(ky, (m,))
    tgt_x = jax.random.normal(kt, (t, CFG.d_embed))
    return ctx_x, ctx_y, tgt_x


# ---------------------------------------------------------------------------
# prefix split
# ---------------------------------------------------------------------------
def test_prefill_decode_matches_monolithic():
    """The neural-process mask makes the context encoding target-
    independent, so prefill+decode must reproduce gpo_apply."""
    params = _params(0)
    ctx_x, ctx_y, tgt_x = _icl(1)
    mu_ref, _ = gpo_apply(params, CFG, ctx_x, ctx_y, tgt_x)
    prefix = gpo_prefill(params, CFG, ctx_x, ctx_y)
    mu_split, _ = gpo_decode(params, CFG, prefix, tgt_x)
    assert prefix.k.shape == (CFG.num_layers, 6, CFG.num_heads,
                              CFG.d_model // CFG.num_heads)
    np.testing.assert_allclose(np.asarray(mu_split), np.asarray(mu_ref),
                               rtol=1e-5, atol=1e-6)


def test_prefill_padded_ctx_len_equivalence():
    """Padding context rows past ctx_len must not change predictions —
    the masked padded keys never participate as attention keys."""
    params = _params(0)
    ctx_x, ctx_y, tgt_x = _icl(2, m=6)
    prefix = gpo_prefill(params, CFG, ctx_x, ctx_y)
    mu_ref, _ = gpo_decode(params, CFG, prefix, tgt_x)
    pad_x = jnp.concatenate([ctx_x, jnp.full((5, CFG.d_embed), 7.0)])
    pad_y = jnp.concatenate([ctx_y, jnp.full((5,), -3.0)])
    prefix_p = gpo_prefill(params, CFG, pad_x, pad_y, ctx_len=6)
    mu_pad, _ = gpo_decode(params, CFG, prefix_p, tgt_x, ctx_len=6)
    np.testing.assert_allclose(np.asarray(mu_pad), np.asarray(mu_ref),
                               rtol=1e-5, atol=1e-6)


def test_decode_matches_monolithic_under_vmap():
    params = _params(0)
    batches = [_icl(k, m=6, t=10) for k in range(3, 6)]
    cx = jnp.stack([b[0] for b in batches])
    cy = jnp.stack([b[1] for b in batches])
    tx = jnp.stack([b[2] for b in batches])
    prefix = jax.vmap(lambda a, b: gpo_prefill(params, CFG, a, b))(cx, cy)
    mu = jax.vmap(lambda k, v, t: gpo_decode(
        params, CFG, GPOPrefix(k=k, v=v), t)[0])(prefix.k, prefix.v, tx)
    for i, (a, b, t) in enumerate(batches):
        ref, _ = gpo_apply(params, CFG, a, b, t)
        np.testing.assert_allclose(np.asarray(mu[i]), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# int8 quantization + kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,k,n", [(7, 16, 5), (64, 128, 64),
                                   (130, 200, 257), (1, 8, 1)])
def test_int8_matmul_matches_oracle(m, k, n):
    kx, kw = jax.random.split(jax.random.PRNGKey(m * 1000 + n), 2)
    x = jax.random.normal(kx, (m, k))
    ql = quantize_linear(jax.random.normal(kw, (k, n)))
    got = int8_matmul(x, ql.q, ql.scale)
    want = ref_int8_matmul(x, ql.q, ql.scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_quantize_linear_roundtrip_error_bound():
    """Symmetric per-output-channel int8: dequant error per element is at
    most half a quantization step of that column."""
    w = jax.random.normal(jax.random.PRNGKey(0), (96, 48))
    ql = quantize_linear(w)
    assert ql.q.dtype == jnp.int8 and ql.scale.shape == (48,)
    err = np.abs(np.asarray(dequantize_linear(ql)) - np.asarray(w))
    step = np.asarray(ql.scale)[None, :]
    assert (err <= 0.5 * step + 1e-7).all()


def test_quantize_gpo_params_structure():
    """Only dense matmul weights become QuantizedLinear; stacked norm
    scales stay f32 and the tree still drives gpo_apply (via _mm)."""
    params = _params(0)
    qp = quantize_gpo_params(params)
    assert isinstance(qp["in_proj"], QuantizedLinear)
    assert isinstance(qp["head"], QuantizedLinear)
    assert isinstance(qp["layers"].wq, QuantizedLinear)
    assert qp["layers"].wq.q.shape[0] == CFG.num_layers  # stacked axis
    assert qp["layers"].ln1.dtype == jnp.float32
    assert not isinstance(qp["layers"].ln1, QuantizedLinear)
    assert qp["final_norm"].dtype == jnp.float32
    ctx_x, ctx_y, tgt_x = _icl(7)
    mu_q, _ = gpo_apply(qp, CFG, ctx_x, ctx_y, tgt_x)
    mu_f, _ = gpo_apply(params, CFG, ctx_x, ctx_y, tgt_x)
    assert np.isfinite(np.asarray(mu_q)).all()
    # int8 weights perturb, but do not destroy, the f32 prediction
    assert 0.0 < np.abs(np.asarray(mu_q) - np.asarray(mu_f)).max() < 0.25


def test_int8_predictions_within_tolerance():
    """The documented serving tolerance (DESIGN.md §12): int8 preference
    rows stay within 0.05 max-abs of f32 on normalized outputs."""
    params = _params(0)
    ctx_x, ctx_y, tgt_x = _icl(8, m=6, t=10)
    f32 = predict_preferences(params, CFG, ctx_x, ctx_y, tgt_x,
                              num_options=5)
    q = predict_preferences(quantize_gpo_params(params), CFG, ctx_x,
                            ctx_y, tgt_x, num_options=5)
    rows = np.asarray(q)
    np.testing.assert_allclose(rows.sum(-1), 1.0, rtol=1e-5)
    assert np.abs(rows - np.asarray(f32)).max() < 0.05


# ---------------------------------------------------------------------------
# engine: batching, cache, scheduling, admission
# ---------------------------------------------------------------------------
def _request(rid, key, m=6, t=10, prefix_key=None):
    ctx_x, ctx_y, tgt_x = _icl(key, m=m, t=t)
    return Request(rid=rid, ctx_x=np.asarray(ctx_x),
                   ctx_y=np.asarray(ctx_y), tgt_x=np.asarray(tgt_x),
                   prefix_key=prefix_key)


def test_ragged_batch_equals_one_at_a_time():
    """A fused ragged batch must produce the same rows as serving each
    request alone (padding + bucketing are numerically invisible)."""
    params = _params(0, scale=2.0)  # avoid clip-saturated uniform rows
    reqs = [_request(0, 10, m=6, t=10), _request(1, 11, m=14, t=5),
            _request(2, 12, m=3, t=8)]
    srv = PreferenceServer(params, CFG, SCFG, num_options=5)
    for r in reqs:
        srv.submit(r)
    batched = {c.rid: c.pred for c in srv.step()}
    assert len(srv.batches) == 1 and srv.batches[0].batch_pad == 4
    solo_cfg = ServeConfig(max_batch=1, batch_buckets=(1,),
                           ctx_buckets=(20, 40), tgt_buckets=(10, 20),
                           cache_entries=0)
    for r in reqs:
        solo = PreferenceServer(params, CFG, solo_cfg, num_options=5)
        solo.submit(r)
        np.testing.assert_allclose(solo.step()[0].pred, batched[r.rid],
                                   rtol=1e-5, atol=1e-6)
        assert batched[r.rid].shape == (r.tgt_x.shape[0] // 5, 5)


@contextlib.contextmanager
def _backend_compiles():
    """Collect the backend compilations that happen inside the block."""
    seen = []

    def on(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on)


def test_mixed_bucket_hits_compile_nothing_new():
    """Once (4, 40, 20) and (4, 80, 20) have been served, a batch of hits
    whose entries own buckets 40 and 80, in any order, reuses the
    compiled programs, and each row equals the request served alone."""
    params = _params(0, scale=2.0)
    own40 = [_request(i, 60 + i, m=m, t=t, prefix_key=("a", i))
             for i, (m, t) in enumerate([(24, 20), (36, 10), (30, 15),
                                         (40, 20)])]
    own80 = [_request(4 + i, 70 + i, m=m, t=t, prefix_key=("b", i))
             for i, (m, t) in enumerate([(60, 15), (45, 20), (80, 10),
                                         (52, 20)])]
    srv = PreferenceServer(params, CFG, WIDE, num_options=5)
    for group, ctx_b in ((own40, 40), (own80, 80)):
        for r in group:
            srv.submit(r)
        srv.step()
        assert (srv.batches[-1].batch_pad, srv.batches[-1].ctx_bucket,
                srv.batches[-1].tgt_bucket) == (4, ctx_b, 20)

    def again(r, rid):
        return Request(rid=rid, ctx_x=r.ctx_x, ctx_y=r.ctx_y,
                       tgt_x=r.tgt_x, prefix_key=r.prefix_key)

    orders = ([own40[0], own80[0], own40[1], own80[1]],
              [own80[2], own40[2], own80[3], own40[3]],
              [own80[1], own80[0], own40[1], own40[0]])
    served = {}
    with _backend_compiles() as compiles:
        for n, order in enumerate(orders):
            for r in order:
                srv.submit(again(r, 100 * (n + 1) + r.rid))
            for c in srv.step():
                served.setdefault(c.rid % 100, []).append(c.pred)
            rec = srv.batches[-1]
            assert all(rec.hits)
            assert (rec.batch_pad, rec.ctx_bucket, rec.tgt_bucket) == (4, 80,
                                                                       20)
    assert compiles == []
    solo_cfg = ServeConfig(max_batch=1, batch_buckets=(1,),
                           ctx_buckets=WIDE.ctx_buckets,
                           tgt_buckets=WIDE.tgt_buckets, cache_entries=0)
    for r in own40 + own80:
        solo = PreferenceServer(params, CFG, solo_cfg, num_options=5)
        solo.submit(r)
        alone = solo.step()[0].pred
        for pred in served[r.rid]:
            np.testing.assert_allclose(alone, pred, rtol=1e-5, atol=1e-6)
    assert sorted(served) == list(range(8))


def test_engine_matches_predict_preferences():
    params = _params(0, scale=2.0)
    r = _request(0, 20)
    srv = PreferenceServer(params, CFG, SCFG, num_options=5)
    srv.submit(r)
    pred = srv.step()[0].pred
    ref = predict_preferences(params, CFG, r.ctx_x, r.ctx_y, r.tgt_x,
                              num_options=5)
    np.testing.assert_allclose(pred, np.asarray(ref), rtol=1e-5,
                               atol=1e-6)


def test_prefix_cache_hit_bit_equal_to_miss():
    """The cache stores the prefill output at the request's own ctx
    bucket, so a hit replays the identical decode inputs: bit-equal."""
    params = _params(0, scale=2.0)
    srv = PreferenceServer(params, CFG, SCFG, num_options=5)
    a = _request(0, 30, prefix_key="g7")
    b = _request(1, 30, prefix_key="g7")  # same context, fresh arrival
    srv.submit(a)
    cold = srv.step()[0]
    srv.submit(b)
    warm = srv.step()[0]
    assert not cold.cache_hit and warm.cache_hit
    assert srv.stats.cache_hits == 1 and srv.stats.cache_misses == 1
    assert srv.stats.prefills == 1  # the hit skipped prefill entirely
    assert np.array_equal(cold.pred, warm.pred)


def test_prefix_cache_hit_bit_equal_to_miss_at_a_larger_bucket():
    """An entry prefilled at its own bucket of 40 is stored with exact
    zeros from row 40 to the largest bucket; a hit on it in a batch at
    bucket 80 is bit-equal to its cold miss in the same batch shape."""
    params = _params(0, scale=2.0)
    srv = PreferenceServer(params, CFG, WIDE, num_options=5)
    short = _request(0, 31, m=30, t=10, prefix_key="own40")
    long = _request(1, 32, m=60, t=10, prefix_key="own80")
    for r in (short, long):
        srv.submit(r)
    cold = srv.step()
    k, v, ctx_len = srv._cache["own40"]
    assert ctx_len == 30
    assert k.shape[1] == v.shape[1] == WIDE.ctx_buckets[-1]
    for a in (np.asarray(k), np.asarray(v)):
        assert np.all(a[:, 40:] == 0.0) and np.any(a[:, :40] != 0.0)
    for r in (short, long):
        srv.submit(Request(rid=r.rid + 10, ctx_x=r.ctx_x, ctx_y=r.ctx_y,
                           tgt_x=r.tgt_x, prefix_key=r.prefix_key))
    warm = srv.step()
    first, second = srv.batches
    assert first.hits == (False, False) and second.hits == (True, True)
    assert first.ctx_bucket == second.ctx_bucket == 80
    assert srv.stats.prefills == 2
    for c, w in zip(cold, warm):
        assert np.array_equal(c.pred, w.pred)


def test_prefix_cache_hit_independent_of_batch_composition():
    """Prefill-at-own-bucket: the cached entry (and thus a hit's result)
    must not depend on which other requests shared the cold batch."""
    params = _params(0, scale=2.0)
    probe = _request(99, 40, m=6, t=10, prefix_key="shared")

    def serve_after_cold_batch(extra_ctx_len):
        srv = PreferenceServer(params, CFG, SCFG, num_options=5)
        srv.submit(_request(0, 41, m=6, t=10, prefix_key="shared"))
        srv.submit(_request(1, 42, m=extra_ctx_len, t=5))
        srv.step()
        srv.submit(probe)
        return srv.step()[0]

    small = serve_after_cold_batch(3)   # cold batch padded to ctx 20
    large = serve_after_cold_batch(15)  # cold batch padded to ctx 20 too
    assert small.cache_hit and large.cache_hit
    assert np.array_equal(small.pred, large.pred)


def test_cache_lru_eviction():
    cfg = ServeConfig(max_batch=1, batch_buckets=(1,), ctx_buckets=(20,),
                      tgt_buckets=(10, 20), cache_entries=2)
    srv = PreferenceServer(_params(0), CFG, cfg, num_options=5)
    for i, key in enumerate(["a", "b", "c"]):
        srv.submit(_request(i, 50 + i, prefix_key=key))
        srv.step()
    assert srv.stats.evictions == 1
    srv.submit(_request(3, 50, prefix_key="a"))  # evicted -> miss again
    srv.step()
    assert srv.stats.cache_hits == 0 and srv.stats.cache_misses == 4


def test_scheduler_deterministic_batch_composition():
    """A fixed arrival trace yields a fixed batch composition — FIFO
    order, bucket choices, pad sizes, and hit flags are all replayed."""
    data = make_survey_data(SurveyConfig(num_groups=6, num_questions=40))
    trace = make_request_trace(data, list(range(6)), num_requests=13,
                               hit_ratio=0.4, seed=5)
    params = init_gpo_params(GPOConfig(d_embed=data.phi.shape[-1]),
                             jax.random.PRNGKey(0))

    def run():
        srv = PreferenceServer(
            params, GPOConfig(d_embed=data.phi.shape[-1]),
            ServeConfig(max_batch=4, batch_buckets=(1, 2, 4),
                        ctx_buckets=(40, 80), tgt_buckets=(20, 40)),
            num_options=data.num_options)
        srv.run_trace(trace)
        return srv.batches

    first, second = run(), run()
    assert first == second
    assert [b.rids for b in first] == [
        (0, 1, 2, 3), (4, 5, 6, 7), (8, 9, 10, 11), (12,)]
    assert first[-1].batch_pad == 1


def test_admission_rejects_when_queue_full():
    cfg = ServeConfig(max_queue=2, ctx_buckets=(20,), tgt_buckets=(10,))
    srv = PreferenceServer(_params(0), CFG, cfg, num_options=5)
    results = [srv.submit(_request(i, 60 + i)) for i in range(5)]
    assert results == [True, True, False, False, False]
    assert srv.stats.rejected == 3 and srv.queue_depth == 2
    srv.step()  # drains the queue, admitting again
    assert srv.submit(_request(9, 69))


def test_request_trace_hit_ratio_and_shapes():
    data = make_survey_data(SurveyConfig(num_groups=6, num_questions=40))
    trace = make_request_trace(data, [0, 1, 2], num_requests=20,
                               hit_ratio=0.75, rate=100.0, seed=1)
    assert len(trace) == 20
    assert len({r.prefix_key for r in trace}) == 5  # ceil(0.25 * 20)
    for r in trace:
        assert r.ctx_x.shape[0] % data.num_options == 0
        assert r.tgt_x.shape[0] % data.num_options == 0
        assert r.ctx_x.shape[0] == r.ctx_y.shape[0]
    arrivals = [r.arrival for r in trace]
    assert arrivals == sorted(arrivals) and arrivals[1] == pytest.approx(0.01)


def test_serve_config_validation():
    with pytest.raises(ValueError):
        ServeConfig(ctx_buckets=()).validate()
    with pytest.raises(ValueError):
        ServeConfig(ctx_buckets=(40, 40)).validate()
    with pytest.raises(ValueError):
        ServeConfig(max_batch=16, batch_buckets=(1, 8)).validate()
    with pytest.raises(ValueError):
        # tgt bucket not a multiple of num_options
        PreferenceServer(_params(0), CFG,
                         ServeConfig(tgt_buckets=(7,)), num_options=5)


# ---------------------------------------------------------------------------
# checkpoint restore contract
# ---------------------------------------------------------------------------
def test_restore_roundtrip_served_outputs_bit_equal(tmp_path):
    """Train briefly, checkpoint, restore: the served predictions must be
    bit-equal to the post-train ones (the serving contract)."""
    data = make_survey_data(SurveyConfig(num_groups=6, num_questions=40))
    tr, ev = split_groups(data)
    gcfg = GPOConfig(d_embed=data.phi.shape[-1])
    fed = FederatedGPO(gcfg, FedConfig(num_clients=len(tr), rounds=2),
                       data, tr, ev)
    fed.run(rounds=2)
    params = fed.global_params
    path = save_checkpoint(str(tmp_path), 2, params)
    like = init_gpo_params(gcfg, jax.random.PRNGKey(0))
    restored = restore_checkpoint(path, like)

    trace = make_request_trace(data, list(ev), num_requests=4, seed=9)
    scfg = ServeConfig(ctx_buckets=(40, 80), tgt_buckets=(20, 40))

    def serve(p):
        srv = PreferenceServer(p, gcfg, scfg,
                               num_options=data.num_options)
        return {c.rid: c.pred for c in srv.run_trace(trace)}

    before, after = serve(params), serve(restored)
    for rid in before:
        assert np.array_equal(before[rid], after[rid])


def test_restore_quantized_leaf_types(tmp_path):
    params = _params(0)
    path = save_checkpoint(str(tmp_path), 1, params)
    qp = restore_checkpoint_quantized(path, params)
    assert isinstance(qp["head"], QuantizedLinear)
    assert qp["layers"].w1.q.dtype == jnp.int8
    assert qp["layers"].ln2.dtype == jnp.float32
    mu, _ = gpo_apply(qp, CFG, *_icl(3))
    assert np.isfinite(np.asarray(mu)).all()


def test_serve_restore_missing_checkpoint_clear_error(tmp_path):
    from repro.launch.serve import _restore_params

    with pytest.raises(SystemExit, match="no checkpoint under"):
        _restore_params(str(tmp_path / "empty"), CFG, seed=0)


def test_serve_restore_corrupt_checkpoint_clear_error(tmp_path):
    from repro.launch.serve import _restore_params

    (tmp_path / "ckpt_00000001.npz").write_bytes(b"not a real npz")
    with pytest.raises(SystemExit, match="unreadable or does not match"):
        _restore_params(str(tmp_path), CFG, seed=0)


def test_serve_restore_shape_mismatch_clear_error(tmp_path):
    from repro.launch.serve import _restore_params

    other = init_gpo_params(
        GPOConfig(d_embed=16, d_model=64, num_layers=2, num_heads=4,
                  d_ff=64), jax.random.PRNGKey(0))
    save_checkpoint(str(tmp_path), 1, other)
    with pytest.raises(SystemExit, match="does not match"):
        _restore_params(str(tmp_path), CFG, seed=0)


def test_serve_restore_flipped_byte_clear_error(tmp_path):
    """Silent corruption AFTER a durable save: the CRC32 content check
    fails as ValueError inside restore_checkpoint and rides
    _restore_params' actionable SystemExit path."""
    from repro.launch.serve import _restore_params

    params = init_gpo_params(CFG, jax.random.PRNGKey(0))
    path = save_checkpoint(str(tmp_path), 1, params)
    raw = bytearray(open(path, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(raw))
    with pytest.raises(SystemExit, match="unreadable or does not match"):
        _restore_params(str(tmp_path), CFG, seed=0)


# ---------------------------------------------------------------------------
# per-request deadlines (DESIGN.md §12)
# ---------------------------------------------------------------------------
def test_expired_head_of_line_requests_dropped():
    """Queued requests whose deadline already passed must be dropped at
    dispatch — counted in stats.expired, never decoded, never completed
    — while live requests behind them still serve."""
    srv = PreferenceServer(_params(0), CFG, SCFG, num_options=5)
    dead = [_request(i, 30 + i) for i in range(2)]
    for r in dead:
        r.deadline = -1.0  # already expired on the engine clock
        srv.submit(r)
    live = _request(7, 40)
    live.deadline = srv.now() + 60.0  # comfortably in the future
    srv.submit(live)
    out = srv.step()
    assert [c.rid for c in out] == [7]
    assert srv.stats.expired == 2
    assert srv.stats.completed == 1
    # the dropped rids never reached a batch record
    assert all(0 not in b.rids and 1 not in b.rids for b in srv.batches)


def test_expired_mid_queue_requests_dropped():
    """Regression: expiry once only checked the HEAD of the queue
    (``_queue[0]``), so an expired request sitting behind a fresh head
    was still decoded and returned after its deadline. Batch assembly
    must skip expired entries ANYWHERE in the queue (counted in
    stats.expired, never decoded) while the live requests keep strict
    FIFO order — the no-reorder determinism contract."""
    srv = PreferenceServer(_params(0), CFG, SCFG, num_options=5)
    head = _request(0, 70)
    head.deadline = srv.now() + 60.0  # fresh head shields the queue
    srv.submit(head)
    stale = _request(1, 71)
    stale.deadline = -1.0  # already expired, BEHIND the fresh head
    srv.submit(stale)
    srv.submit(_request(2, 72))  # no deadline: live
    out = srv.step()
    assert [c.rid for c in out] == [0, 2]  # FIFO among live requests
    assert srv.stats.expired == 1
    assert srv.stats.completed == 2
    assert all(1 not in b.rids for b in srv.batches)


def test_deadline_none_never_expires():
    """Requests without a deadline keep the pre-deadline behavior
    exactly: nothing is dropped, stats.expired stays 0."""
    srv = PreferenceServer(_params(0), CFG, SCFG, num_options=5)
    for i in range(3):
        srv.submit(_request(i, 50 + i))
    out = srv.step()
    assert sorted(c.rid for c in out) == [0, 1, 2]
    assert srv.stats.expired == 0


def test_all_expired_queue_drains_without_batch():
    """A queue of only-expired work drains to nothing: step() returns []
    and dispatches no batch (no decode slot is wasted)."""
    srv = PreferenceServer(_params(0), CFG, SCFG, num_options=5)
    for i in range(3):
        r = _request(i, 55 + i)
        r.deadline = -1.0
        srv.submit(r)
    assert srv.step() == []
    assert srv.stats.expired == 3 and not srv.batches
    assert srv.queue_depth == 0
