"""The token-text scoring cell (``granite-4-h-small-gpo.bestofn-text``):
its FLOP counter against a count by hand, and a whole run without the
look for a chip, at small widths on the CPU: sound, it is correct; with
the backbone broken underneath, ``correct`` comes out false.

Faults, one per way the backbone can leave out or add mathematics: the
shared expert left out; the routed experts dropping the pairs past a
capacity of 1.0 (``ceil(T * k / E)`` per expert, the dropping MoE's
rule); rotary position embeddings applied in a NoPE attention layer.

The same faults, and the sound run, read on the chip at the cell's own
sizes:

    python chipbench/tests/test_text_cell.py --fault shared_expert_left_out \
        --seeds 1 2 3 --seconds 5
"""
import argparse
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import tiny

CELL = "granite-4-h-small-gpo.bestofn-text"
CONFIG = "granite-4-h-small-gpo.json"
# the backbone at small widths: one period of (mamba, attention, mamba),
# 8 of 16 experts held, texts of 4-32 tokens; in float32, so that what a
# fault moves is not lost in the rounding of bfloat16 at these widths
SMALL = {"dtype": "float32", "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "attention_multiplier": 0.0625,
         "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
         "intermediate_size": 32, "shared_intermediate_size": 48,
         "num_local_experts_published": 16, "num_local_experts": 8,
         "num_experts_per_tok": 2, "vocab_size": 512,
         "layer_types": ["mamba", "attention", "mamba"],
         "num_hidden_layers": 3, "ssd_chunk": 8}


def make_tiny(dest: Path) -> Path:
    """``tiny.make`` with the backbone and its texts cut down too."""
    root = tiny.make(dest)
    f = root / "chipbench" / "configs" / CONFIG
    c = json.loads(f.read_text())
    c.update(SMALL)
    c["serve"].update(text_buckets=[8, 16, 32], text_row_buckets=[12, 20, 40])
    f.write_text(json.dumps(c))
    f = root / "chipbench" / "traffic" / "bestofn-text.json"
    t = json.loads(f.read_text())
    t.update(text_tokens={"median": 12, "log_sd": 0.6, "min": 4, "max": 32},
             check_requests=4, survey_check_texts=4)
    f.write_text(json.dumps(t))
    return root


def shared_expert_left_out(setattr):
    """Every layer's shared expert adds nothing."""
    import repro.models.transformer as tf

    setattr(tf, "shared_expert", lambda p, x: jnp.zeros_like(x))


def experts_dropped_at_capacity(setattr):
    """Each expert takes at most ceil(T * k / E) of a call's pairs, in
    token order; the pairs past that are dropped (their gate is 0)."""
    import repro.models.moe as moe

    real = moe.route

    def capped(p, xf, k, aux_coef):
        top_idx, gates, aux = real(p, xf, k, aux_coef)
        e = p.router.shape[1]
        cap = math.ceil(xf.shape[0] * k / e)
        onehot = jax.nn.one_hot(top_idx.reshape(-1), e, dtype=jnp.int32)
        before = jnp.cumsum(onehot, axis=0) - onehot
        slot = jnp.take_along_axis(before, top_idx.reshape(-1, 1), axis=1)
        keep = (slot[:, 0] < cap).reshape(gates.shape)
        return top_idx, jnp.where(keep, gates, 0.0), aux

    setattr(moe, "route", capped)


def rope_applied(setattr):
    """The attention layers rotate q and k (RoPE, theta 10,000), though
    the configuration has no positional encoding."""
    import repro.models.attention as attention

    def roped(q, k, positions, theta, cfg):
        return (attention.rope(q, positions, theta),
                attention.rope(k, positions, theta))

    setattr(attention, "_rope_qk", roped)


FAULTS = {"shared_expert_left_out": shared_expert_left_out,
          "experts_dropped_at_capacity": experts_dropped_at_capacity,
          "rope_applied": rope_applied}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_tiny(tmp_path_factory.mktemp("bench"))


@pytest.fixture
def fresh():
    """Compiled programs do not outlive a test: a fault is traced into
    them."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_flops_by_hand():
    from chipbench.flops import granite_h

    cfg = {"hidden_size": 8, "mamba_n_heads": 2, "mamba_d_head": 8,
           "mamba_d_state": 4, "mamba_n_groups": 1,
           "num_attention_heads": 2, "num_key_value_heads": 1,
           "num_local_experts_published": 4, "num_local_experts": 2,
           "num_experts_per_tok": 2, "intermediate_size": 3,
           "shared_intermediate_size": 5,
           "layer_types": ["mamba", "attention"]}
    n = 3
    # mamba: in_proj to 2*16 + 2*4 + 2 = 42 columns, 2*3*8*42 = 2016;
    # SSD 4*3*2*8*4 = 768; out_proj 2*3*16*8 = 768
    mamba = 2016 + 768 + 768
    # attention: head 4; q, k, v 2*3*8*(2+2)*4 = 768; o 2*3*8*8 = 384;
    # 6 causal pairs, scores and sums 2*2*6*2*4 = 192
    attention = 768 + 384 + 192
    # ffn: router 2*3*8*4 = 192; experts 3 tokens * 2 chosen * 2/4 held
    # = 3 pairs, each 3*2*8*3 = 144 -> 432; shared 3*2*3*8*5 = 720
    ffn = 192 + 432 + 720
    assert granite_h.text(cfg, n) == mamba + attention + 2 * ffn
    assert granite_h.expert_rows(cfg, 3) == 432


def test_sound_run_is_correct(root, fresh):
    out = tiny.run(root, CELL)
    assert out["correct"], out["checks"]
    assert out["checks"]["compiles_in_window"]["value"] == 0
    assert out["metrics"]["serve_rps"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(root, fresh, monkeypatch, fault):
    FAULTS[fault](monkeypatch.setattr)
    out = tiny.run(root, CELL)
    assert not out["correct"], out["checks"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="read a fault at the cell's size")
    ap.add_argument("--fault", choices=sorted(FAULTS) + ["none"],
                    required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(tiny.ROOT / "src"), str(tiny.ROOT)]
    from chipbench import run as harness

    if args.fault != "none":
        FAULTS[args.fault](setattr)
    for seed in args.seeds:
        out = harness.run(["--workload", CELL, "--seed", str(seed),
                           "--seconds", str(args.seconds)],
                          root=Path(tiny.ROOT))
        print(json.dumps({"workload": CELL, "fault": args.fault,
                          "seed": seed, "correct": out["correct"],
                          "checks": out["checks"],
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
