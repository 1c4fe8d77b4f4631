"""The readers of the program's own spans, on records made by hand."""
import sys
from pathlib import Path

import pytest

from chipbench import program_spans
from chipbench.run import load_module
from repro.utils.spans import Span

READERS = Path(__file__).resolve().parents[1] / "layer_metrics"
MS = 1_000_000  # ns


def reader(stem):
    return load_module(READERS / f"{stem}.py").read


def serve_record():
    """Two steps: 10 ms and 12 ms, waiting 1 and 3 ms, gathering 2 ms
    each; one prefill of 30 real rows in 40 and decodes of 20 in 40 and
    40 in 40."""
    rec = []

    def add(name, start, end, parent=None, **counts):
        rec.append(Span(name, start * MS, end * MS, parent, counts))
        return len(rec) - 1

    s = add("serve.step", 0, 10, batch=0, requests=4)
    add("serve.admit", 0, 1, s)
    add("serve.prefill", 1, 3, s, contexts=2, rows=30, computed=40)
    add("serve.gather", 3, 5, s)
    add("serve.decode", 5, 6, s, rows=20, computed=40)
    add("serve.wait", 6, 7, s)
    add("serve.complete", 7, 10, s)
    s = add("serve.step", 20, 32, batch=1, requests=8)
    add("serve.gather", 21, 23, s)
    add("serve.decode", 23, 25, s, rows=40, computed=40)
    add("serve.wait", 25, 28, s)
    return rec


def fed_record():
    """One ``run`` of 60 ms in two blocks, fetching for 20 and 10 ms."""
    rec = [Span("fed.run", 0, 60 * MS, None, {"rounds": 20})]
    for start, fetch in ((0, 20), (30, 10)):
        rec.append(Span("fed.block", start * MS, (start + 30) * MS, 0,
                        {"rounds": 10}))
        b = len(rec) - 1
        rec.append(Span("fed.dispatch", start * MS, (start + 2) * MS, b, {}))
        rec.append(Span("fed.fetch", (start + 2) * MS,
                        (start + 2 + fetch) * MS, b, {}))
        rec.append(Span("fed.record", (start + 2 + fetch) * MS,
                        (start + 3 + fetch) * MS, b, {}))
    return rec


@pytest.mark.parametrize("stem,record,want", [
    ("step_host_ms", serve_record, (10 - 1 + 12 - 3) / 2),
    ("step_wait_ms", serve_record, (1 + 3) / 2),
    ("kv_gather_ms", serve_record, 2.0),
    ("engine_pad_share", serve_record, 100.0 * (1 - 90 / 120)),
    ("block_host_ms", fed_record, (60 - 30) / 2),
])
def test_reader_on_a_hand_made_record(monkeypatch, stem, record, want):
    monkeypatch.setattr(program_spans, "recorded", record)
    assert reader(stem)(None) == pytest.approx(want)


@pytest.mark.parametrize("stem", ["step_host_ms", "step_wait_ms",
                                  "kv_gather_ms", "engine_pad_share",
                                  "block_host_ms"])
def test_reader_reads_nothing(monkeypatch, stem):
    """``None`` without the program's span module, with an empty record,
    and where the record holds none of the spans the reader needs."""
    with monkeypatch.context() as m:
        m.setitem(sys.modules, "repro.utils.spans", None)  # not importable
        assert program_spans.recorded() is None
        assert reader(stem)(None) is None
    from repro.utils import spans

    spans.clear()
    assert program_spans.recorded() is None
    assert reader(stem)(None) is None
    other = fed_record if stem != "block_host_ms" else serve_record
    monkeypatch.setattr(program_spans, "recorded", other)
    assert reader(stem)(None) is None


def test_open_spans_are_left_out(monkeypatch):
    from repro.utils import spans

    monkeypatch.setattr(spans, "recorded", lambda: [
        Span("serve.step", 0, None, None, {}),
        Span("serve.wait", 0, 2 * MS, 0, {})])
    (only,) = program_spans.recorded()
    assert only.name == "serve.wait"
