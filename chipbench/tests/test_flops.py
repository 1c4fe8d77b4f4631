"""The FLOP and byte counters against counts made by hand."""
import jax
import jax.numpy as jnp
import pytest

from chipbench.flops import gpo, int8_matmul

SMALL = {"d_embed": 6, "d_model": 4, "num_layers": 1, "num_heads": 2,
         "d_ff": 8, "learn_sigma": False}


def test_forward_by_hand():
    # m=3 context, t=2 target tokens, S=5:
    # in_proj 2*5*8*4 = 320; q,k,v,o 2*5*4*4*4 = 640;
    # keys 3*3 + 2*(3+1) = 17, attention 2*17*4*2 = 272;
    # mlp 2*5*4*8*2 = 640; head 2*2*4*1 = 16
    assert gpo.forward(SMALL, 3, 2) == 320 + 640 + 272 + 640 + 16


def test_train_step_by_hand():
    # backward twice the forward, less the input projection's input grad
    assert gpo.train_step(SMALL, 3, 2) == 3 * 1888 - 320


def test_prefill_and_decode_by_hand():
    # prefill: in_proj 2*3*8*4 = 192; proj 2*3*4*4*4 = 384;
    # attention 2*9*4*2 = 144; mlp 2*3*4*8*2 = 384
    assert gpo.prefill(SMALL, 3) == 192 + 384 + 144 + 384
    # decode: in_proj 2*2*8*4 = 128; proj 2*2*4*4*4 = 256;
    # keys 2*(3+1) = 8, attention 2*8*4*2 = 128; mlp 256; head 16
    assert gpo.decode(SMALL, 3, 2) == 128 + 256 + 128 + 256 + 16
    # the two halves make one forward pass
    assert gpo.prefill(SMALL, 3) + gpo.decode(SMALL, 3, 2) == gpo.forward(
        SMALL, 3, 2)


def test_int8_call_by_hand():
    # batch 2 of x (20, 4098) by q (4098, 1): row block 20, K pads to
    # 4104, N to a column block of 8
    assert int8_matmul.call(2, 20, 4098, 1) == 2 * 2 * 20 * 4104 * 8


def test_int8_pass_counts_every_dense_weight():
    # d_embed 6, d_model 4, d_ff 8, 1 layer, 2 examples of 3 rows (row
    # block 8): in_proj K 8 N 8; wq wk wv wo K 8 N 8; w1 K 8 N 8;
    # w2 K 8 N 8; head K 8 N 8 -- every dimension pads to 8
    per = 2 * 2 * 8 * 8 * 8
    assert int8_matmul.gpo_pass(SMALL, 2, 3, head=True) == 8 * per
    assert int8_matmul.gpo_pass(SMALL, 2, 3, head=False) == 7 * per


@pytest.mark.parametrize("m,k,n", [(20, 4098, 128), (40, 128, 256),
                                   (160, 256, 128), (3, 130, 1)])
def test_int8_padding_matches_the_kernel(m, k, n):
    """The shapes the count assumes are the shapes the kernel is given."""
    from repro.kernels.quant_matmul import int8_matmul_flat

    jaxpr = jax.make_jaxpr(lambda x, q, s: int8_matmul_flat(
        x, q, s, interpret=True))(
        jnp.zeros((m, k)), jnp.zeros((k, n), jnp.int8), jnp.zeros((n,)))
    call = next(e for e in jaxpr.jaxpr.eqns
                if e.primitive.name == "pallas_call")
    x_shape, q_shape = (v.aval.shape for v in call.invars[:2])
    flops = int8_matmul.call(1, m, k, n)
    assert flops == 2 * x_shape[0] * x_shape[1] * q_shape[1]
    assert x_shape[1] == q_shape[0]
