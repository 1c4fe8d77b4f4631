"""Compile the Pallas kernels of the main path for a described TPU v5e.

Interpret mode cannot see what the chip's compiler refuses (block shapes
off the (8, 128) tiling, too much VMEM), so each kernel here is lowered
and compiled natively at the paper's sizes for a v5e that is described,
not attached. Nothing runs. The topology is described inside a fixture,
never at import, so every xdist worker collects the same tests and only
the worker that runs this file loads the TPU compiler.

Sizes: S = (16 + 16) questions x 5 options = 160 attention points,
``GPOConfig`` heads H = 4 of width hd = 32, C = 10 clients and
P = 1,050,112 parameters (the predictor at ``d_embed=4096``). The
dropless MoE's grouped expert products (the TPU's ``ragged-dot`` custom
call) compile at granite-4.0-h-small's widths: 12 texts of 128 tokens
routed top-10 over 72 experts, 18 of them held.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ops import (
    agg_clip_reduce,
    agg_momentum_reduce,
    agg_pairwise_dists,
    agg_quant_clip_reduce,
    agg_trimmed_reduce,
    fedavg_reduce,
    gpo_attention,
    int8_matmul,
)
from repro.models.moe import MoEParams, moe_dropless

S, H, HD, NUM_CTX = 160, 4, 32, 80
C, P = 10, 1_050_112
M, K, N = 640, 4098, 128  # served target points x (d_embed + 2) x d_model
T, D, E, HELD, FF = 1536, 4096, 72, 18, 768  # granite-4.0-h-small MoE


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # no compiler logs outside
    prev_cache = jax.config.jax_enable_compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev_log


def _attn(q, k, v):
    return gpo_attention(q, k, v, num_ctx=NUM_CTX, interpret=False)


def _attn_fwd_bwd(q, k, v):
    return jax.grad(lambda *a: jnp.sum(_attn(*a) ** 2),
                    argnums=(0, 1, 2))(q, k, v)


QKV = [((S, H, HD), jnp.float32)] * 3
CASES = {
    "gpo_attention_fwd": (_attn, QKV),
    "gpo_attention_fwd_bwd": (_attn_fwd_bwd, QKV),
    "fedavg_reduce": (
        lambda x, w: fedavg_reduce(x, w, interpret=False),
        [((C, P), jnp.float32), ((C,), jnp.float32)]),
    "agg_momentum_reduce": (
        lambda x, w, m: agg_momentum_reduce(x, w, m, beta=0.9,
                                            interpret=False),
        [((C, P), jnp.float32), ((C,), jnp.float32), ((P,), jnp.float32)]),
    "agg_clip_reduce": (
        lambda x, w, z: agg_clip_reduce(x, w, clip=1.0, noise=z,
                                        interpret=False),
        [((C, P), jnp.float32), ((C,), jnp.float32), ((C, P), jnp.float32)]),
    "agg_quant_clip_reduce": (
        lambda x, w, z, u, r: agg_quant_clip_reduce(
            x, w, clip=1.0, noise=z, uniform=u, resid=r, interpret=False),
        [((C, P), jnp.float32), ((C,), jnp.float32)]
        + [((C, P), jnp.float32)] * 3),
    "agg_pairwise_dists": (
        lambda x: agg_pairwise_dists(x, interpret=False),
        [((C, P), jnp.float32)]),
    "agg_trimmed_reduce": (
        lambda x, w: agg_trimmed_reduce(x, w, trim=(C - 1) // 2,
                                        interpret=False),
        [((C, P), jnp.float32), ((C,), jnp.float32)]),
    "int8_matmul": (
        lambda x, q, s: int8_matmul(x, q, s, interpret=False),
        [((M, K), jnp.float32), ((K, N), jnp.int8), ((N,), jnp.float32)]),
    "moe_dropless": (
        lambda x, *p: moe_dropless(MoEParams(*p), x, 10)[0],
        [((1, T, D), jnp.bfloat16), ((D, E), jnp.bfloat16)]
        + [((HELD, D, FF), jnp.bfloat16)] * 2
        + [((HELD, FF, D), jnp.bfloat16)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
