"""A whole run of each cell, without the look for a chip, at small widths
on the CPU: sound, it is correct; with the timed path broken underneath,
``correct`` comes out false.

Faults, one per kind the cell can have: a training step that returns its
state unchanged; half of each training batch left out, the loss the mean
over the rest; a served answer altered where it is produced. (No cell
spans chips, so none can leave out an exchange between them.)

The same faults read on the chip at the cells' own sizes:

    python chipbench/tests/test_faults.py --workload gpo-d4096.fed-paper \
        --fault half_batch --seeds 1 2 3 --seconds 5
"""
import argparse
import json
import sys
from pathlib import Path

import jax
import pytest

import tiny

TRAIN = "gpo-d4096.fed-paper"
SERVE = ["gpo-d4096.bestofn", "gpo-d4096.online", "gpo-d4096-int8.bestofn"]


def state_unchanged(setattr):
    """Every optimizer step returns the parameters it was given."""
    import repro.core.federated as fed
    from repro.optim.optimizers import Optimizer

    real = fed.adam

    def frozen(lr):
        opt = real(lr)
        return Optimizer(init=opt.init, update=lambda g, s, p: (p, s))

    setattr(fed, "adam", frozen)


def half_batch(setattr):
    """The local loss is the mean over the first half of the targets."""
    import repro.core.federated as fed

    real = fed.gpo_loss

    def half(params, cfg, ctx_x, ctx_y, tgt_x, tgt_y):
        n = tgt_x.shape[0] // 2
        return real(params, cfg, ctx_x, ctx_y, tgt_x[:n], tgt_y[:n])

    setattr(fed, "gpo_loss", half)


def answer_altered(setattr):
    """Every request's first answer becomes certain of option 0."""
    import repro.core.serving as serving

    real = serving._decode_batch

    def altered(*args, **kw):
        rows = real(*args, **kw)  # (batch, questions, options)
        return rows.at[:, 0].set(jax.nn.one_hot(0, rows.shape[-1]))

    setattr(serving, "_decode_batch", altered)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "answer_altered": answer_altered}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload", [TRAIN] + SERVE)
def test_sound_run_is_correct(root, workload):
    out = tiny.run(root, workload)
    assert out["correct"], out["checks"]
    assert out["checks"]["compiles_in_window"]["value"] == 0


def test_state_left_unchanged(root, monkeypatch):
    state_unchanged(monkeypatch.setattr)
    out = tiny.run(root, TRAIN)
    assert not out["correct"]
    assert out["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out(root, monkeypatch):
    half_batch(monkeypatch.setattr)
    assert not tiny.run(root, TRAIN)["correct"]


@pytest.mark.parametrize("workload", SERVE)
def test_answer_altered(root, monkeypatch, workload):
    answer_altered(monkeypatch.setattr)
    assert not tiny.run(root, workload)["correct"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="read a fault at a cell's size")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS), required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(tiny.ROOT / "src"), str(tiny.ROOT)]
    from chipbench import run as harness

    FAULTS[args.fault](setattr)
    for seed in args.seeds:
        out = harness.run(["--workload", args.workload, "--seed", str(seed),
                           "--seconds", str(args.seconds)],
                          root=Path(tiny.ROOT))
        print(json.dumps({"workload": args.workload, "fault": args.fault,
                          "seed": seed, "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
