"""granite-4.0-h (Mamba-2/attention hybrid with a dropless MoE after every
mixer) against its plain float32 reference, at a small size on the CPU:
hidden states and pooled embeddings, the expert layer's shares, and the
serving engine's token-text targets."""
import contextlib
import zlib
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ATTN, MAMBA, GPOConfig, ServeConfig, get_arch
from repro.core import PreferenceServer, Request, init_gpo_params
from repro.core.serving import _embed_texts
from repro.models import hidden_states, init_params
from repro.models import reference_granite_h as ref
from repro.models.layers import dense_init
from repro.models.moe import (
    MoEParams,
    init_moe_params,
    moe_dropless,
    shared_expert,
)
from repro.utils import spans

# two whole periods of (mamba, attn, mamba); 4 of 8 experts held here
CFG = replace(
    get_arch("granite-4.0-h-small"), num_layers=6,
    block_pattern=(MAMBA, ATTN, MAMBA), d_model=64, vocab_size=256,
    num_heads=4, num_kv_heads=2, head_dim=16, attn_scale=0.0625,
    d_ff=32, num_experts=8, experts_per_token=3, experts_held=4,
    shared_expert_ff=48, ssm_state_size=16, ssm_head_dim=16, ssm_chunk=8,
    param_dtype="float32", activation_dtype="float32")
# the reference's products are at "highest"; the system's at the CPU's
# float32 default, which is float32: the two differ by rounding alone
TOL = 1e-4


def _params(cfg, seed=0):
    """Seeded random weights, with norm scales, conv biases and the
    SSM's D moved off their initial values so every term counts."""
    key = jax.random.PRNGKey(seed + 1)

    def move(path, a):
        # stacked vectors are (layers, n); the embedding table stays
        if a.ndim > 2 or "embed" in jax.tree_util.keystr(path):
            return a
        name = jax.tree_util.keystr(path).encode()
        k = jax.random.fold_in(key, zlib.crc32(name) % 2**31)
        return a + 0.1 * jax.random.normal(k, a.shape, a.dtype)

    return jax.jit(lambda k: jax.tree_util.tree_map_with_path(
        move, init_params(cfg, k)))(jax.random.PRNGKey(seed))


def ref_weights(params, cfg):
    """The program's parameters in the reference's layout."""
    seen = {MAMBA: 0, ATTN: 0}
    layers = []
    for kind in cfg.layer_kinds():
        lp = jax.tree.map(lambda a: a[seen[kind]], params["layers"][kind])
        seen[kind] += 1
        moe, sh = lp["moe"], lp["shared"]
        w = {"ln2": lp["ln2"], "router": moe.router, "w_gate": moe.w_gate,
             "w_up": moe.w_up, "w_down": moe.w_down,
             "shared_gate": sh["w_gate"], "shared_up": sh["w_up"],
             "shared_down": sh["w_down"]}
        if kind == MAMBA:
            w.update(lp["mamba"]._asdict(), ln=lp["ln"])
        else:
            a = lp["attn"]
            w.update(ln=lp["ln1"], wq=a.wq, wk=a.wk, wv=a.wv, wo=a.wo)
        layers.append(w)
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "layers": layers}


def ref_config(cfg) -> dict:
    return {"layer_types": ["mamba" if k == MAMBA else "attention"
                            for k in cfg.layer_kinds()],
            "mamba_n_heads": cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim,
            "mamba_d_head": cfg.ssm_head_dim,
            "mamba_d_state": cfg.ssm_state_size,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "attention_multiplier": cfg.attn_scale,
            "num_experts_per_tok": cfg.experts_per_token,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "rms_norm_eps": cfg.norm_eps}


def rel_gap(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


@pytest.fixture(scope="module")
def params():
    return _params(CFG)


def test_hidden_states_match_reference(params):
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 20), 0,
                                CFG.vocab_size)
    hidden, _, rows = jax.jit(hidden_states, static_argnums=1)(
        params, CFG, tokens)
    w, rc = ref_weights(params, CFG), ref_config(CFG)
    for b in range(2):
        want, _ = ref.hidden_states(w, rc, tokens[b])
        assert rel_gap(hidden[b], want) < TOL
    # every token routes 3 pairs; about half reach the 4 held experts
    assert 0 < int(rows) < 6 * 2 * 20 * 3


def test_pooled_embedding_matches_reference(params):
    """Two texts (20 and 13 tokens) packed into one row of 40, two texts
    a row, and a padding row: each text pools as the reference does it
    alone."""
    lens = [20, 13]
    texts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(4), (2, 24), 0, CFG.vocab_size), np.int32)
    tokens = np.zeros((2, 40), np.int32)
    segs = np.zeros((2, 40), np.int32)
    tokens[0, :20], tokens[0, 20:33] = texts[0, :20], texts[1, :13]
    segs[0, :20], segs[0, 20:33] = 1, 2
    unit, pooled, _ = _embed_texts(params, CFG, tokens, segs,
                                   jnp.zeros((), jnp.int32), 2)
    w, rc = ref_weights(params, CFG), ref_config(CFG)
    for b in range(2):
        _, want = ref.hidden_states(w, rc, texts[b], lens[b])
        assert rel_gap(pooled[b], want) < TOL
        np.testing.assert_allclose(np.linalg.norm(unit[b]), 1.0, rtol=1e-5)
    assert not np.any(np.asarray(pooled[2:]))  # a padding row pools to 0


def test_expert_shares_add_up_to_the_whole_layer():
    """Four chips each hold 2 of 8 experts: their shares, with the shared
    expert counted once, are the uncut reference's MoE + shared output."""
    cfg = replace(CFG, experts_held=0)
    k_moe, k_sh, k_x = jax.random.split(jax.random.PRNGKey(7), 3)
    moe = init_moe_params(k_moe, cfg, jnp.float32)
    sh = dict(zip(("w_gate", "w_up", "w_down"), (
        dense_init(k, shape) for k, shape in zip(
            jax.random.split(k_sh, 3),
            [(cfg.d_model, 48), (cfg.d_model, 48), (48, cfg.d_model)]))))
    x = jax.random.normal(k_x, (2, 9, cfg.d_model))

    @jax.jit
    def shares(moe, sh, x):
        total = shared_expert(sh, x)
        for first in range(0, 8, 2):
            part = MoEParams(moe.router, *(
                a[first:first + 2] for a in (moe.w_gate, moe.w_up,
                                             moe.w_down)))
            out, _, _ = moe_dropless(part, x, cfg.experts_per_token,
                                     first=first)
            total = total + out
        return total

    @jax.jit
    def whole(moe, sh, x):
        rc = ref_config(cfg)
        return jax.vmap(lambda xb: ref._moe(moe._asdict(), rc, xb, None)
                        + ref._swiglu(xb, sh["w_gate"], sh["w_up"],
                                      sh["w_down"], None))(x)

    assert rel_gap(shares(moe, sh, x), whole(moe, sh, x)) < TOL


GPO = GPOConfig(d_embed=CFG.d_model, d_model=32, num_layers=2, num_heads=4,
                d_ff=64)
SCFG = ServeConfig(max_batch=4, batch_buckets=(1, 2, 4), ctx_buckets=(20,),
                   tgt_buckets=(5, 10), cache_entries=8,
                   text_buckets=(16, 32), text_row_buckets=(4, 8))
A = 5


def _server(params):
    return PreferenceServer(init_gpo_params(GPO, jax.random.PRNGKey(0)), GPO,
                            SCFG, num_options=A, embedder=(CFG, params))


def _texts(seed):
    rng = np.random.default_rng(seed)
    lens = rng.integers(3, 30, A).astype(np.int32)
    toks = rng.integers(0, CFG.vocab_size, (A, 32)).astype(np.int32)
    return toks, lens


def _context(seed):
    rng = np.random.default_rng(seed)
    cx = rng.normal(size=(15, GPO.d_embed)).astype(np.float32)
    cx /= np.linalg.norm(cx, axis=1, keepdims=True)
    return cx, rng.uniform(size=15).astype(np.float32)


def test_token_texts_serve_as_their_embeddings(params):
    srv = _server(params)
    cx, cy = _context(1)
    toks, lens = _texts(2)
    srv.submit(Request(rid=0, ctx_x=cx, ctx_y=cy, tgt_tokens=toks,
                       tgt_lens=lens, prefix_key="g"))
    (texts_out,) = srv.step()
    unit, _ = srv.embed([t[:n] for t, n in zip(toks, lens)])
    srv.submit(Request(rid=1, ctx_x=cx, ctx_y=cy, tgt_x=unit,
                       prefix_key="g"))
    (emb_out,) = srv.step()
    np.testing.assert_array_equal(texts_out.pred, emb_out.pred)
    # the two kinds of request in one batch
    srv.submit(Request(rid=2, ctx_x=cx, ctx_y=cy, tgt_tokens=toks,
                       tgt_lens=lens, prefix_key="g"))
    srv.submit(Request(rid=3, ctx_x=cx, ctx_y=cy, tgt_x=unit,
                       prefix_key="g"))
    both = srv.step()
    assert [c.rid for c in both] == [2, 3]
    for c in both:
        np.testing.assert_array_equal(c.pred, texts_out.pred)


def test_text_request_needs_an_embedder(params):
    srv = PreferenceServer(init_gpo_params(GPO, jax.random.PRNGKey(0)), GPO,
                           SCFG, num_options=A)
    cx, cy = _context(1)
    toks, lens = _texts(2)
    with pytest.raises(ValueError, match="embedder"):
        srv.submit(Request(rid=0, ctx_x=cx, ctx_y=cy, tgt_tokens=toks,
                           tgt_lens=lens))


@contextlib.contextmanager
def _profiled(trace_dir):
    spans.clear()
    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def test_embed_spans_count_texts_and_tokens(params, tmp_path):
    srv = _server(params)
    cx, cy = _context(1)
    rng = np.random.default_rng(5)
    toks = rng.integers(0, CFG.vocab_size, (2, A, 32)).astype(np.int32)
    # five texts of each length bucket (16 and 32 tokens)
    lens = np.array([[4, 20, 9, 30, 16], [25, 3, 31, 17, 12]], np.int32)

    def submit():
        for rid in range(2):
            srv.submit(Request(rid=rid, ctx_x=cx, ctx_y=cy,
                               tgt_tokens=toks[rid], tgt_lens=lens[rid],
                               prefix_key="g"))

    submit()
    srv.step()  # compile outside the capture
    submit()
    with _profiled(tmp_path):
        srv.step()
    rec = spans.recorded()
    embeds = [s for s in rec if s.name == "serve.embed"]
    # the 10 texts share rows of 32 tokens, 32 // 16 = 2 a row: 6 rows
    # (first fit decreasing), padded to the row bucket of 8, in one call
    assert [(s.counts["texts"], s.counts["rows"], s.counts["computed"])
            for s in embeds] == [(10, 6, 8 * 32)]
    assert sum(s.counts["tokens"] for s in embeds) == int(lens.sum())
    steps = {i for i, s in enumerate(rec) if s.name == "serve.step"}
    assert all(s.parent in steps for s in embeds)
