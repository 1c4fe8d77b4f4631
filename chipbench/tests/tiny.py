"""A copy of the benchmark at small widths, for runs on the CPU."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
MODEL = {"d_embed": 64, "d_model": 32, "num_layers": 2, "num_heads": 2,
         "d_ff": 64}


def make(dest: Path) -> Path:
    """``dest`` holds BENCHMARK.json, the benchmark and a link to the
    program; configurations shrink to ``MODEL``, windows and pools too.
    Limits are the committed ones."""
    shutil.copytree(ROOT / "chipbench", dest / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (dest / "src").symlink_to(ROOT / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if "gpo-d4096.online" not in {w["name"] for w in bench["workloads"]}:
        # the open loop's cell is out of BENCHMARK.json until its host
        # stalls are understood (PERF.md); its driver stays under test
        bench["workloads"].append({
            "name": "gpo-d4096.online", "config": "gpo-d4096",
            "traffic": "online-unique", "chips": 1,
            "why": "fresh contexts at a fixed Poisson rate"})
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    for f in (dest / "chipbench" / "configs").glob("*.json"):
        c = json.loads(f.read_text())
        c["model"].update(MODEL)
        # the CPU's default precision for float32 products is float32
        c["reference_products"] = "highest"
        c["served_weights"]["steps"] = 100
        f.write_text(json.dumps(c))
    for f in (dest / "chipbench" / "traffic").glob("*.json"):
        t = json.loads(f.read_text())
        if t["driver"] == "fed":
            t["rounds_per_call"] = 10
        else:
            t.update(pool=32, warmup_seconds=0.2, check_requests=16)
            if t["driver"] == "open":
                t["rate"] = 50
        f.write_text(json.dumps(t))
    return dest


def run(root: Path, workload: str, seed: int = 5, seconds: float = 1.0):
    from chipbench import run as harness

    return harness.run(["--workload", workload, "--seed", str(seed),
                        "--seconds", str(seconds), "--trace", "0"],
                       require_tpu=False, root=root)
