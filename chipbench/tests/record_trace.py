"""Record the small trace that ``test_trace.py`` reads: three steps of the
int8 serving engine at the benchmark's widths, under the benchmark's host
spans. Run on the chip:

    python chipbench/tests/record_trace.py chipbench/tests/data/serve3
"""
import glob
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import common  # noqa: E402
from chipbench.serve_lib import program_params  # noqa: E402
from repro.configs import GPOConfig, ServeConfig  # noqa: E402
from repro.core.gpo import GPOLayer  # noqa: E402
from repro.core.serving import PreferenceServer, Request  # noqa: E402


def main(dest: str) -> int:
    model = {"d_embed": 4096, "d_model": 128, "num_layers": 4,
             "num_heads": 4, "d_ff": 256, "norm_eps": 1e-6,
             "learn_sigma": False, "param_dtype": "float32"}
    srv = PreferenceServer(
        program_params(common.make_weights(model, 0), GPOLayer),
        GPOConfig(**model), ServeConfig(int8_weights=True), num_options=5)
    rng = np.random.default_rng(0)

    def req(i):
        return Request(rid=i, ctx_x=rng.normal(size=(40, 4096)).astype(
            np.float32), ctx_y=np.full((40,), 0.2, np.float32),
            tgt_x=rng.normal(size=(20, 4096)).astype(np.float32),
            prefix_key=("k", i % 2))

    for i in range(4):  # compile: a miss batch, then a hit batch
        srv.submit(req(i))
        srv.step()
    spans = common.Spans(True)
    tmp = Path(dest) / "raw"
    jax.profiler.start_trace(str(tmp))
    with spans("cb:window"):
        for i in range(3):
            with spans("cb:submit"):
                srv.submit(req(10 + i))
            with spans("cb:step"):
                srv.step()
    jax.profiler.stop_trace()
    src = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    raw = Path(src).read_bytes()
    shutil.rmtree(tmp)
    # the ops' source locations name the checkout; an equal-length
    # placeholder keeps the protobuf's lengths valid
    root = str(ROOT).encode()
    tag = (b"<checkout>" + b"_" * len(root))[:len(root)]
    (Path(dest) / "serve3.xplane.pb").write_bytes(raw.replace(root, tag))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
