"""Chip benchmark of the PluralLLM system: one cell per call.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<traffic>.json``); the mix names its driver
(``chipbench/drivers/<driver>.py``). The driver builds the system from the
seed, warms up every shape it will use, measures for ``--seconds``
seconds, and then compares what the window produced with the plain
reference (``reference.py``) against the cell's limits
(``chipbench/limits/<cell>.json``). With ``--trace 1`` the window is
traced and the per-layer metrics are read by
``chipbench/layer_metrics/<metric>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared with its limit.
The same numbers are the last lines of standard error. Without a TPU, on a
device kind missing from ``peaks.json``, or without the program beside
it, the run prints no result and exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Context:
    """What a driver gets: the cell's files, the run's arguments, the
    compile counter, host spans, and the measured window."""

    def __init__(self, cell, config, traffic, args, compiles, spans):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.traced = args.seed, bool(args.trace)
        # a traced run measures no end-to-end metric: its window is the
        # traffic's ``trace_seconds`` where that is shorter, which keeps
        # the trace small enough to read within the run's time
        self.seconds = (min(args.seconds, traffic["trace_seconds"])
                        if self.traced else args.seconds)
        self.compiles, self.spans = compiles, spans
        self.window_start = None
        self.trace_dir = None

    def mark(self, label: str) -> None:
        """Log how far set-up has come (standard error)."""
        print(f"[{time.perf_counter() - T_START:8.2f} s] {label}; "
              f"{self.compiles}", file=sys.stderr, flush=True)

    @contextlib.contextmanager
    def window(self):
        """The measured window. Set-up ends where it starts; with tracing,
        the profiler records exactly this span. What set-up left on the
        heap is collected once and frozen, so that the collector's full
        passes in the window scan only what the window allocates (without
        it, a full pass over JAX's and the program's objects can stall the
        host loop for a large share of a second)."""
        import jax

        from chipbench.common import GcPauses

        gc.collect()
        gc.freeze()
        self.window_start = time.perf_counter()
        if self.traced:
            self.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(self.trace_dir)
        pauses = GcPauses()
        try:
            with self.spans("cb:window"), pauses:
                yield
        finally:
            if self.traced:
                jax.profiler.stop_trace()
            self.mark(f"window closed; collector in the window: {pauses}")


def cell_metrics(bench: dict, cell: str):
    """The cell's end-to-end metrics, and the per-layer metrics it reads."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def run(argv=None, *, require_tpu: bool = True, root: Path = ROOT) -> dict:
    """One run; returns the result line as a dict. ``require_tpu=False``
    and another ``root`` are for the harness's own tests on the CPU."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (root / "src" / "repro").is_dir():
        raise SystemExit(f"the program is not beside the benchmark "
                         f"({root / 'src' / 'repro'} is missing)")
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench.common import load_cell, reader_path

    bench, cell, config, traffic = load_cell(root, args.workload)
    bdir = root / "chipbench"
    limits = json.loads(
        (bdir / "limits" / f"{cell['name']}.json").read_text())

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    import jax

    from chipbench import common, trace as trace_lib

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX is on {dev.platform}")
    if len(devices) < cell["chips"]:
        raise SystemExit(f"{cell['name']} needs {cell['chips']} chips, JAX "
                         f"sees {len(devices)}")
    peaks = common.peaks_for(dev.device_kind) if require_tpu else {}

    ctx = Context(cell, config, traffic, args, common.CompileCounter(),
                  common.Spans(bool(args.trace)))
    driver = load_module(bdir / "drivers" / f"{traffic['driver']}.py")
    ctx.mark(f"device {dev.device_kind} ready; {cell['name']} set-up starts")
    res = driver.run(ctx)
    setup_s = ctx.window_start - T_START

    e2e, layer = cell_metrics(bench, cell["name"])
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": False, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": {}, "device": device}
    if args.trace:
        summary = trace_lib.reduce_dir(ctx.trace_dir)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        lctx = LayerContext(summary, res["counters"], peaks)
        for m in layer:
            value = load_module(reader_path(root, m["name"])).read(lctx)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value,
                                             "unit": m["unit"]}
        out["breakdown"] = summary.breakdown()
    else:
        values = dict(res["e2e"], setup_s=setup_s)
        for m in e2e:
            out["metrics"][m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}

    checks = dict(res["checks"], compiles_in_window=res["compiles_in_window"])
    limits = dict(limits, compiles_in_window=0)
    out["checks"] = {k: {"value": v, "limit": limits.get(k)}
                     for k, v in checks.items()}
    out["correct"] = (res["attempted"] > 0 and all(
        c["limit"] is not None and math.isfinite(c["value"])
        and c["value"] <= c["limit"] for c in out["checks"].values()))
    return out


class LayerContext:
    """What a per-layer reader gets: the reduced trace, the driver's
    counters over the traced window, and the chip's peaks."""

    def __init__(self, trace, counters, peaks):
        self.trace, self.counters, self.peaks = trace, counters, peaks


def main(argv=None) -> int:
    out = run(argv)
    for k, c in out["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct = {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
