"""Host spans of the program (``repro.utils.spans``): recorded only under
a profiler session, nested with their counts, written into the capture's
host plane; the serving engine's and the round driver's spans and
counts; and the round-key chain carried across ``FederatedGPO.run``
calls."""
import contextlib
import glob
import sys
import threading
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs import FedConfig, GPOConfig, ServeConfig
from repro.core import FederatedGPO, PreferenceServer, Request, init_gpo_params
from repro.data import SurveyConfig, make_survey_data, split_groups
from repro.utils import spans

CFG = GPOConfig(d_embed=16, d_model=32, num_layers=2, num_heads=4, d_ff=64)
SCFG = ServeConfig(max_batch=4, batch_buckets=(1, 2, 4),
                   ctx_buckets=(20, 40), tgt_buckets=(10, 20),
                   cache_entries=16)


@contextlib.contextmanager
def profiled(trace_dir):
    """A profiler session with an empty span record."""
    spans.clear()
    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def closed(name=None):
    return [(i, s) for i, s in enumerate(spans.recorded())
            if s.end_ns is not None and (name is None or s.name == name)]


def children(parent: int, name: str):
    return [s for _, s in closed(name) if s.parent == parent]


def test_no_session_records_nothing():
    spans.clear()
    assert not TraceAnnotation.is_enabled()
    with spans.span("outer", n=1) as s:
        s.set(m=2)
        with spans.span("inner"):
            pass
    assert spans.recorded() == []
    assert spans.span("a") is spans.span("b")  # one shared no-op


def test_nested_spans_record_parents_and_counts(tmp_path):
    with profiled(tmp_path):
        with TraceAnnotation("outer.annotation"):
            with spans.span("step", batch=3) as s:
                with spans.span("decode", rows=40, computed=80):
                    pass
                s.set(requests=2)
                with spans.span("wait"):
                    pass
    rec = spans.recorded()
    assert [s.name for s in rec] == ["step", "decode", "wait"]
    step, decode, wait = rec
    assert step.parent is None and decode.parent == 0 and wait.parent == 0
    assert step.counts == {"batch": 3, "requests": 2}
    assert decode.counts == {"rows": 40, "computed": 80}
    assert step.start_ns <= decode.start_ns <= decode.end_ns \
        <= wait.start_ns <= wait.end_ns <= step.end_ns
    # the same spans in the capture's host plane, inside the outer
    # annotation, with their counts as event stats
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events[e.name] = (e.start_ns, e.end_ns, dict(e.stats))
    outer = events["outer.annotation"]
    for name in ("step", "decode", "wait"):
        assert outer[0] <= events[name][0] <= events[name][1] <= outer[1]
    assert events["decode"][2] == {"rows": 40, "computed": 80}
    assert events["decode"][0] >= events["step"][0]
    assert events["decode"][1] <= events["step"][1]
    spans.clear()
    assert spans.recorded() == []


def test_threads_keep_their_own_parents(tmp_path):
    """Spans opened on many threads at once, switching often: every
    inner span is recorded, under its own thread's outer span."""
    n_threads, n_inner = 16, 50
    together = threading.Barrier(n_threads, timeout=60)

    def work(k):
        with spans.span("outer", thread=k):
            together.wait()  # every outer span is open before any inner
            for i in range(n_inner):
                with spans.span("inner", thread=k, i=i):
                    time.sleep(0)  # let another thread in

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with profiled(tmp_path):
            threads = [threading.Thread(target=work, args=(k,))
                       for k in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    rec = spans.recorded()
    inner = [s for s in rec if s.name == "inner"]
    assert len(inner) == n_threads * n_inner
    for s in inner:
        outer = rec[s.parent]
        assert outer.name == "outer"
        assert outer.counts["thread"] == s.counts["thread"]


def test_record_is_capped(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "MAX_RECORDED", 3)
    with profiled(tmp_path):
        for i in range(5):
            with spans.span("s", i=i):
                pass
    assert [s.counts["i"] for s in spans.recorded()] == [0, 1, 2]


def _request(rid, seed, m, t, prefix_key=None):
    kx, ky, kt = jax.random.split(jax.random.PRNGKey(seed), 3)
    return Request(rid=rid,
                   ctx_x=np.asarray(jax.random.normal(kx, (m, CFG.d_embed))),
                   ctx_y=np.asarray(jax.random.uniform(ky, (m,))),
                   tgt_x=np.asarray(jax.random.normal(kt, (t, CFG.d_embed))),
                   prefix_key=prefix_key)


def _trace():
    """Ragged requests over two context buckets, with shared prefixes:
    misses in one and in two buckets, a key shared within a batch, hits,
    and a last batch of one."""
    lens = [(6, 10, "a"), (14, 5, "b"), (30, 15, "c"), (6, 10, "a"),
            (25, 20, None), (14, 10, "b"), (3, 5, "d"), (30, 5, "c"),
            (8, 10, None)]
    return [_request(i, 100 + i, m, t, key)
            for i, (m, t, key) in enumerate(lens)]


def _serve(requests):
    params = jax.tree.map(lambda a: 2.0 * a,
                          init_gpo_params(CFG, jax.random.PRNGKey(0)))
    srv = PreferenceServer(params, CFG, SCFG, num_options=5)
    for r in requests:
        srv.submit(r)
    out = {}
    while srv.queue_depth:
        out.update({c.rid: c.pred for c in srv.step()})
    return srv, out


def test_server_same_rows_and_batches_under_profiler(tmp_path):
    srv_off, rows_off = _serve(_trace())
    with profiled(tmp_path):
        srv_on, rows_on = _serve(_trace())
    assert srv_on.batches == srv_off.batches
    assert rows_on.keys() == rows_off.keys()
    for rid in rows_off:
        np.testing.assert_array_equal(rows_on[rid], rows_off[rid])
    names = {s.name for _, s in closed()}
    assert names == {"serve.step", "serve.admit", "serve.prefill",
                     "serve.gather", "serve.decode", "serve.wait",
                     "serve.complete"}


def test_batch_record_prefill_groups():
    srv, _ = _serve(_trace())
    # batch 1 (rids 0-3): a, b at bucket 20 and c at 40 miss; rid 3
    # shares a's key in the batch. Batch 2 (4-7): the keyless request
    # and d miss at 40 and 20; b and c hit. Batch 3 (8): keyless.
    assert [b.rids for b in srv.batches] == [(0, 1, 2, 3), (4, 5, 6, 7),
                                             (8,)]
    assert [b.prefills for b in srv.batches] == [
        ((20, 2, 2), (40, 1, 1)), ((20, 1, 1), (40, 1, 1)), ((20, 1, 1),)]
    assert sum(c for b in srv.batches for _, _, c in b.prefills) \
        == srv.stats.prefills


def test_prefill_and_decode_counts_match_padding(tmp_path):
    reqs = _trace()
    with profiled(tmp_path):
        srv, _ = _serve(reqs)
    by_rid = {r.rid: r for r in reqs}
    steps = closed("serve.step")
    assert [s.counts["batch"] for _, s in steps] == [0, 1, 2]
    for (i, step), b in zip(steps, srv.batches):
        assert step.counts["requests"] == len(b.rids)
        (decode,) = children(i, "serve.decode")
        assert decode.counts == {
            "rows": sum(by_rid[r].tgt_x.shape[0] for r in b.rids),
            "computed": b.batch_pad * b.tgt_bucket}
        prefills = children(i, "serve.prefill")
        assert [(p.counts["computed"], p.counts["contexts"])
                for p in prefills] == [(cb * gb, n)
                                       for cb, gb, n in b.prefills]
        seen, rows = set(), 0
        for rid, hit in zip(b.rids, b.hits):
            key = by_rid[rid].prefix_key
            if not hit and (key is None or key not in seen):
                rows += by_rid[rid].ctx_x.shape[0]
            seen.add(key)
        assert sum(p.counts["rows"] for p in prefills) == rows
        for name in ("serve.admit", "serve.gather", "serve.wait",
                     "serve.complete"):
            assert len(children(i, name)) == 1


def _fed(seed=3):
    data = make_survey_data(SurveyConfig(
        num_groups=6, num_questions=32, d_embed=16, seed=seed))
    tr, ev = split_groups(data, seed=seed)
    fcfg = FedConfig(num_clients=len(tr), rounds=20, local_epochs=1,
                     eval_every=5, num_context=4, num_target=4, seed=seed)
    return FederatedGPO(CFG, fcfg, data, tr, ev)


def test_round_driver_spans(tmp_path, capsys):
    fed = _fed()
    with profiled(tmp_path):
        hist = fed.run(rounds=20, log_every=10)
    assert len(hist.round_loss) == 20
    ((run_i, run),) = closed("fed.run")
    assert run.counts == {"rounds": 20} and run.parent is None
    blocks = closed("fed.block")
    assert len(blocks) == 2
    for i, block in blocks:
        assert block.parent == run_i and block.counts == {"rounds": 10}
        for name in ("fed.dispatch", "fed.fetch", "fed.record"):
            assert len(children(i, name)) == 1
    assert not closed("fed.round")  # no tail: 20 rounds in blocks of 10


@pytest.mark.parametrize("engine", ["scan", "loop"])
def test_run_calls_continue_the_key_chain(engine, capsys):
    whole = _fed().run(rounds=20, log_every=10, engine=engine)
    fed = _fed()
    first = fed.run(rounds=10, log_every=10, engine=engine)
    second = fed.run(rounds=10, log_every=10, engine=engine)
    # the second call continues where the first stopped: the same
    # program on the same state and keys, so the same losses bit for bit
    np.testing.assert_array_equal(first.round_loss + second.round_loss,
                                  whole.round_loss)
