"""The reduction from trace to metrics, on intervals made by hand."""
from pathlib import Path

import pytest

from chipbench import trace


def test_union_merges_and_clips():
    got = trace.union([(0.5, 2.0), (1.0, 3.0), (4.0, 5.0), (-1.0, 0.2)],
                      0.0, 4.5)
    assert got == [(0.0, 0.2), (0.5, 3.0), (4.0, 4.5)]


def test_gaps_are_the_complement():
    assert trace.gaps([(1.0, 2.0), (3.0, 4.0)], 0.0, 5.0) == [
        (0.0, 1.0), (2.0, 3.0), (4.0, 5.0)]


def test_summary_busy_idle_and_attribution():
    # window 0..10 s; the device runs 1-3, 2-4 (overlap) and 6-7;
    # the host steps 0.5-4.5, submits 4.5-6, waits 7-10
    device = [[("fusion", 1.0, 3.0), ("fusion", 2.0, 4.0),
               ("int8_matmul_kernel", 6.0, 7.0)]]
    spans = [("cb:window", 0.0, 10.0), ("cb:step", 0.5, 4.5),
             ("cb:submit", 4.5, 6.0), ("cb:idle", 7.0, 10.0)]
    s = trace.summarize(device, spans)
    assert s.window_s == 10.0
    assert s.busy_s == pytest.approx(4.0)  # 1-4 and 6-7
    # gaps 0-1 (step 0.5 of it, no other span), 4-6 (submit 1.5,
    # step 0.5), 7-10 (idle)
    assert s.idle_by_span == pytest.approx(
        {"cb:step": 1.0, "cb:submit": 2.0, "cb:idle": 3.0})
    assert s.kernel_seconds("int8_matmul") == pytest.approx(1.0)
    assert s.op_seconds["fusion"] == pytest.approx(4.0)
    b = s.breakdown()
    assert b["device_ops"][0] == ["fusion", pytest.approx(4.0)]
    assert b["idle_gaps"][0] == ["cb:idle", pytest.approx(3.0)]


def test_gap_that_no_span_covers_is_other():
    s = trace.summarize([[("op", 0.0, 1.0)]],
                        [("cb:window", 0.0, 2.0), ("cb:step", 0.0, 1.0)])
    assert s.idle_by_span == {"other": pytest.approx(1.0)}


def test_two_devices_average():
    s = trace.summarize([[("op", 0.0, 1.0)], [("op", 0.0, 3.0)]],
                        [("cb:window", 0.0, 4.0)])
    assert s.busy_s == pytest.approx(2.0)


DATA = Path(__file__).resolve().parent / "data" / "serve3.xplane.pb"


def _busy_by_sweep(intervals, lo, hi):
    """Busy time by a sweep over sorted end points (a second way)."""
    points = sorted([(max(s, lo), 1) for s, e in intervals if e > lo]
                    + [(min(e, hi), -1) for s, e in intervals if e > lo])
    busy, depth, last = 0.0, 0, lo
    for t, step in points:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_recorded_trace():
    """Three int8 serving steps recorded on a TPU v5e
    (``record_trace.py``): one device, the window and its spans, the
    kernel's 26 calls per decode pass, and the busy time."""
    devices, spans = trace.read_xplane(str(DATA))
    assert len(devices) == 1
    assert [n for n, _, _ in spans].count("cb:step") == 3
    s = trace.summarize(devices, spans)
    (_, lo, hi), = [x for x in spans if x[0] == trace.WINDOW]
    assert s.window_s == pytest.approx(hi - lo)
    assert s.busy_s == pytest.approx(
        _busy_by_sweep([(a, b) for _, a, b in devices[0]], lo, hi))
    assert 0 < s.busy_s < s.window_s
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s)
    # one decode pass: in_proj, 4 layers x 6 dense weights, head
    kernels = [n for n, _, _ in devices[0] if "int8_matmul" in n]
    assert len(kernels) == 3 * 26
    assert all(n.startswith("jit__decode_batch ") for n in kernels)
    assert not any(trace.CONTROL_FLOW.search(k) for k in s.op_seconds)


def test_every_per_layer_metric_has_a_reader():
    """Each per-layer metric of ``BENCHMARK.json`` finds a reader by its
    name or by its family's stem."""
    import json

    from chipbench import common
    from chipbench.run import load_module

    root = Path(__file__).resolve().parents[2]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        path = common.reader_path(root, m["name"])
        assert path.exists(), m["name"]
        assert callable(load_module(path).read), m["name"]
    assert common.reader_path(root, "idle_share.train").name == (
        "idle_share.py")
    assert common.reader_path(root, "pad_share.closed").name == (
        "pad_share.closed.py")
