"""Launch-layer units: sharding rules, specs, HLO cost engine, roofline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ALL_ARCHS, INPUT_SHAPES, get_arch
from repro.launch.hlo_cost import analyze_hlo, parse_module
from repro.launch.roofline import model_flops, parse_collectives
from repro.launch.sharding import param_spec
from repro.launch.specs import (
    batch_specs,
    cache_specs,
    count_params,
    input_specs,
    serving_config,
)


class FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 16, "model": 16}


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_param_specs_divisibility(arch):
    """Every sharded dim must be divisible by its mesh axes product."""
    cfg = get_arch(arch)
    shapes = jax.eval_shape(
        lambda k: __import__("repro.models", fromlist=["init_params"])
        .init_params(cfg, k), jax.random.PRNGKey(0))
    mesh = FakeMesh()
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    for path, leaf in flat:
        spec = param_spec(jax.tree_util.keystr(path), tuple(leaf.shape),
                          cfg, mesh, fsdp=True)
        for dim, axis in zip(leaf.shape, spec):
            if axis is None:
                continue
            axes = axis if isinstance(axis, tuple) else (axis,)
            total = int(np.prod([mesh.shape[a] for a in axes]))
            assert dim % total == 0, (arch, path, leaf.shape, spec)


@pytest.mark.parametrize("arch", ALL_ARCHS)
@pytest.mark.parametrize("shape", sorted(INPUT_SHAPES))
def test_input_specs_cover_all_inputs(arch, shape):
    cfg = serving_config(get_arch(arch), INPUT_SHAPES[shape])
    specs = input_specs(cfg, INPUT_SHAPES[shape])
    sh = INPUT_SHAPES[shape]
    if sh.kind == "train":
        b = specs["batch"]
        assert "labels" in b
        key = "embeds" if cfg.input_kind == "embeddings" else "tokens"
        assert b[key].shape[0] == sh.global_batch
        assert b[key].shape[1] == sh.seq_len
        if cfg.is_encoder_decoder:
            assert b["enc_embeds"].shape[1] == cfg.enc_seq_len
    elif sh.kind == "prefill":
        assert "labels" not in specs["batch"]
    else:
        assert specs["tokens"].shape == (sh.global_batch, 1)
        assert len(specs["cache"]) > 0


def test_long500k_variant_only_for_full_attention():
    for arch in ALL_ARCHS:
        cfg = get_arch(arch)
        served = serving_config(cfg, INPUT_SHAPES["long_500k"])
        if cfg.long_context_variant:
            assert max(served.window_pattern) <= cfg.long_context_window
        else:
            assert served.window_pattern == cfg.window_pattern


def test_hlo_cost_trip_count_awareness():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=13)
        return y.sum()

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    hlo = jax.jit(f).lower(x, w).compile().as_text()
    totals = analyze_hlo(hlo)
    expected = 13 * 2 * 32 ** 3
    assert 0.95 * expected < totals.flops < 1.2 * expected
    # XLA's own analysis counts the body once — our reason to exist
    xla = jax.jit(f).lower(x, w).compile().cost_analysis()
    if isinstance(xla, (list, tuple)):  # pre-0.5 jax wraps it in a list
        xla = xla[0] if xla else {}
    if "flops" not in xla:  # don't let the undercount claim pass vacuously
        pytest.skip("cost_analysis() reports no flops on this backend")
    assert xla["flops"] < totals.flops / 5


def test_parse_module_entry():
    hlo = jax.jit(lambda a: a * 2 + 1).lower(
        jax.ShapeDtypeStruct((8,), jnp.float32)).compile().as_text()
    comps, entry = parse_module(hlo)
    assert entry is not None and entry in comps


def test_collective_regex():
    text = """
  %ar = f32[16,512]{1,0} all-reduce(%x), replica_groups={}
  %ag.1 = bf16[2,128]{1,0} all-gather(%y), dimensions={0}
  %rs = (f32[8,8]{1,0}, f32[8,8]{1,0}) reduce-scatter(%a, %b)
"""
    stats = parse_collectives(text)
    assert stats.bytes_by_kind["all-reduce"] == 16 * 512 * 4
    assert stats.bytes_by_kind["all-gather"] == 2 * 128 * 2
    assert stats.bytes_by_kind["reduce-scatter"] == 2 * 8 * 8 * 4


def test_collective_regex_counts_opcodes_not_operand_names():
    """A tuple all-reduce is read back through get-tuple-element lines
    that name it as an operand; only the collective itself moves bytes.
    An async pair counts once, at its -done. TPU tile layouts put
    parentheses inside a tuple result."""
    text = """
  %all-reduce.1 = (f32[32]{0}, f32[18,32]{1,0}) all-reduce(%a, %b), to_apply=%r
  %get-tuple-element.3 = f32[32]{0} get-tuple-element(%all-reduce.1), index=0
  %get-tuple-element.4 = f32[18,32]{1,0} get-tuple-element(%all-reduce.1), index=1
  %all-gather-start = (f32[8]{0}, f32[64]{0}) all-gather-start(%c), dimensions={0}
  %all-gather-done = f32[64]{0} all-gather-done(%all-gather-start)
  %fusion.2 = f32[64]{0} fusion(%all-gather-done), kind=kLoop
  %all-reduce.14 = (f32[128]{0:T(128)S(1)}, f32[4,128]{1,0:T(4,128)S(1)}) all-reduce(%p, %q), channel_id=1
"""
    stats = parse_collectives(text)
    assert stats.bytes_by_kind == {
        "all-reduce": (32 + 18 * 32 + 128 + 4 * 128) * 4,
        "all-gather": 64 * 4}
    assert stats.count_by_kind == {"all-reduce": 2, "all-gather": 1}


def test_model_flops_moe_counts_active_only():
    dense = get_arch("qwen2-0.5b")
    moe = get_arch("grok-1-314b")
    f_moe = model_flops(moe, INPUT_SHAPES["train_4k"], 256)
    n_total = count_params(moe)
    # active params far below total for 8-expert top-2
    assert f_moe < 6 * n_total * INPUT_SHAPES["train_4k"].global_batch \
        * INPUT_SHAPES["train_4k"].seq_len / 256
    assert f_moe > 0
    assert model_flops(dense, INPUT_SHAPES["decode_32k"], 256) > 0


def test_count_params_sane():
    assert 0.4e9 < count_params(get_arch("qwen2-0.5b")) < 0.7e9
    assert 250e9 < count_params(get_arch("grok-1-314b")) < 400e9
    assert 20e9 < count_params(get_arch("gemma2-27b")) < 35e9
