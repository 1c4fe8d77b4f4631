"""Run the full (architecture x input-shape) dry-run sweep, resumably.

  PYTHONPATH=src python -m repro.launch.sweep --out results/dryrun.jsonl
  PYTHONPATH=src python -m repro.launch.sweep --multi-pod --out results/dryrun_mp.jsonl

Each pair is lowered+compiled in-process; results append as JSON lines.
Already-recorded (arch, shape, multi_pod) triples are skipped, so the sweep
can be re-launched after interruption. ``main`` forces 512 host-platform
devices before JAX starts its backend; importing the module does not.
"""

import argparse
import gc
import json
import os
import time
import traceback


def done_keys(path: str) -> set:
    keys = set()
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if "error" not in r:
                    keys.add((r["arch"], r["shape"], r.get("multi_pod",
                                                           False)))
    return keys


def main() -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--arch", default=None, help="restrict to one arch")
    ap.add_argument("--shape", default=None, help="restrict to one shape")
    args = ap.parse_args()

    from repro.configs import ALL_ARCHS, INPUT_SHAPES
    from repro.launch.dryrun import lower_pair

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    done = done_keys(args.out)
    archs = [args.arch] if args.arch else list(ALL_ARCHS)
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    todo = [(a, s) for a in archs for s in shapes
            if (a, s, args.multi_pod) not in done]
    print(f"sweep: {len(todo)} pairs to run (skipping {len(done)} done)")

    for i, (arch, shape) in enumerate(todo):
        t0 = time.time()
        print(f"[{i+1}/{len(todo)}] {arch} x {shape} "
              f"multi_pod={args.multi_pod}", flush=True)
        try:
            result = lower_pair(arch, shape, multi_pod=args.multi_pod,
                                verbose=False)
            status = "ok"
        except Exception:
            result = {"arch": arch, "shape": shape,
                      "multi_pod": args.multi_pod,
                      "error": traceback.format_exc()}
            status = "ERROR"
        with open(args.out, "a") as f:
            f.write(json.dumps(result) + "\n")
        print(f"   -> {status} in {time.time()-t0:.0f}s", flush=True)
        import jax

        jax.clear_caches()
        gc.collect()
    print("sweep complete")


if __name__ == "__main__":
    main()
