"""From a profiler trace to the numbers the per-layer metrics read.

``read_xplane`` takes the device's operation intervals and the
benchmark's host spans (``cb:`` names, written by ``Spans``) out of the
``.xplane.pb`` file that ``jax.profiler`` writes. ``summarize`` reduces
them: busy time is the union of the device intervals inside the window,
each idle gap of the device is put to the host span that overlaps it most,
and kernel time is the sum of the durations of the events that carry a
kernel's name.
"""
from __future__ import annotations

import bisect
import glob
import re
from dataclasses import dataclass, field

WINDOW = "cb:window"
PREFIX = "cb:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTROL_FLOW = re.compile(r" %(while|conditional|call)[.\s]")


def union(intervals, lo, hi):
    """Sorted, disjoint (start, end) intervals covering ``intervals``
    clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def gaps(busy, lo, hi):
    """The complement of ``busy`` (sorted, disjoint) within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def attribute(gap_list, spans):
    """Seconds of device idle time per host span name. A gap goes to the
    span (other than the window) that overlaps it most; a gap that no
    span overlaps goes to ``other``."""
    spans = sorted((s, e, name) for name, s, e in spans if name != WINDOW)
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0.0)
    totals = {}
    for gs, ge in gap_list:
        best, best_ov = "other", 0.0
        j = bisect.bisect_left(starts, ge) - 1
        while j >= 0 and starts[j] >= gs - longest:
            s, e, name = spans[j]
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov:
                best, best_ov = name, ov
            j -= 1
        totals[best] = totals.get(best, 0.0) + (ge - gs)
    return totals


@dataclass
class Summary:
    window_s: float
    busy_s: float
    idle_by_span: dict = field(default_factory=dict)  # name -> seconds
    op_seconds: dict = field(default_factory=dict)  # device op name -> s

    def kernel_seconds(self, name: str) -> float:
        return sum(v for k, v in self.op_seconds.items() if name in k)

    def breakdown(self) -> dict:
        top = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
            d.items(), key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(self.op_seconds),
                "idle_gaps": top(self.idle_by_span)}


def summarize(device_events, host_spans) -> Summary:
    """``device_events``: (name, start, end) per device, a list of lists;
    ``host_spans``: (name, start, end). Times in seconds on one clock.
    Busy and idle times are averaged over the devices. Operation times
    leave out control flow (a loop's event spans the operations of its
    body, which are counted themselves)."""
    win = [s for s in host_spans if s[0] == WINDOW]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(win)}")
    _, lo, hi = win[0]
    busy_total, idle, ops = 0.0, {}, {}
    for events in device_events:
        busy = union([(s, e) for _, s, e in events], lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for k, v in attribute(gaps(busy, lo, hi), host_spans).items():
            idle[k] = idle.get(k, 0.0) + v / len(device_events)
        for name, s, e in events:
            d = min(e, hi) - max(s, lo)
            if d > 0 and not CONTROL_FLOW.search(name):
                ops[name] = ops.get(name, 0.0) + d / len(device_events)
    return Summary(window_s=hi - lo, busy_s=busy_total / len(device_events),
                   idle_by_span=idle, op_seconds=ops)


def op_name(module: str, op: str) -> str:
    """A short stable label: the program's name without its hash, and the
    HLO instruction's name and result type (``XLA Ops`` events are named
    by the instruction's whole text)."""
    return f"{module.split('(')[0]} {op.split('{')[0][:72]}"


def label_ops(modules, ops):
    """(label, start, end) for each op, labelled with the module whose
    execution it lies in. Both lists are (name, start, end)."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = modules[i][0] if i >= 0 and s < modules[i][2] else "?"
        out.append((op_name(mod, name), s, e))
    return out


def read_xplane(path: str):
    """(device_events, host_spans) of one ``.xplane.pb``, in seconds.
    The devices are the planes named ``/device:TPU:<n>``; their
    operations are the events of the line named ``XLA Ops``, each
    labelled with the program (``XLA Modules``) it ran in."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and name[12:].isdigit():
            lines = {line.name: [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)}
            devices.append(label_ops(lines.get(MODULES_LINE, []),
                                     lines.get(OPS_LINE, [])))
        elif name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                             for e in line.events
                             if e.name.startswith(PREFIX))
    return devices, spans


def reduce_dir(trace_dir: str) -> Summary:
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace file under {trace_dir}, found "
                         f"{len(files)}")
    devices, spans = read_xplane(files[0])
    if not devices or not any(devices):
        raise ValueError("the trace holds no device operations")
    return summarize(devices, spans)
