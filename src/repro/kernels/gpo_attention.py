"""GPO neural-process attention Pallas kernels — the paper's hot spot,
differentiable end-to-end (DESIGN.md §4, §8).

The preference predictor's mask is irregular for a causal flash kernel:
  * context tokens (first m) attend to all context tokens,
  * target tokens attend to context tokens AND themselves only.

TPU-native design (DESIGN.md §4): block the (q, k) plane into MXU-aligned
tiles. The default *banded* grid is ``(h, num_qb, ctx_blocks + 1)``: for
every q-row of tiles the kernel walks only the k-tiles that contain
context columns, plus one final k-step that maps onto the diagonal tile
(target self-attention). The O(S*m + S) work claim therefore holds at the
grid level — the kernel never visits (and never DMAs) the off-diagonal
target×target tiles at all, instead of iterating the full O(S^2/b^2) grid
and predicating tiles away with ``@pl.when`` (the legacy ``banded=False``
grid, kept for A/B benchmarking).

num_ctx is static (it is part of the training configuration, Eq. 1), so
``ctx_blocks`` and the banded grid shape fold at trace time.

Training hot path (DESIGN.md §8): ``gpo_attention_hsd`` carries a
``custom_vjp`` so ``gpo_loss`` under ``jax.grad`` stays on the tiled
band. The forward kernel residualizes ``(o, lse)`` — per-row logsumexp
stats instead of the (h, S, S) probability tensor — and the backward
pass is a ``delta = rowsum(do * o)`` preprocessing step plus two Pallas
kernels that recompute tile scores from q/k on the fly:

  * **dq** on the forward's banded grid ``(h, num_qb, ctx_blocks + 1)``
    — each q-row accumulates over its band's k-tiles;
  * **dk/dv** on the transposed band, flattened to
    ``(h, ctx_blocks*num_qb + (num_kb - ctx_blocks))`` — context k-tiles
    sweep every q-tile (all rows attend context), pure-target k-tiles
    visit only their diagonal q-tile (self-attention is their sole
    consumer).

No O(S^2)-sized tensor is ever materialized in either direction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_default

NEG_INF = -1e30


def _np_tile_mask(q_start, k_start, num_ctx: int, bq: int, bk: int):
    """Neural-process mask for one (bq, bk) tile: key is context, or
    key == query (target self-attention)."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.logical_or(k_pos < num_ctx, k_pos == q_pos)


def _tile_relevant(q_start, k_start, num_ctx: int, bq: int, bk: int):
    """A (q, k) tile is relevant iff it contains context columns or
    touches the diagonal (target self-attention)."""
    return jnp.logical_or(
        k_start < num_ctx,
        jnp.logical_and(k_start < q_start + bq, q_start < k_start + bk))


def _k_step_schedule(i_q, t, *, num_ctx: int, ctx_blocks: int | None,
                     num_kb: int, bq: int, bk: int):
    """(k_start, compute, last) for grid step (q-row i_q, k-step t) —
    the single definition of the per-step schedule shared by the forward
    and dq kernels (their grids MUST agree for gradients to be correct).

    Full grid (``ctx_blocks is None``): k-steps walk every k-tile and
    irrelevant target×target tiles are predicated off. Banded grid:
    k-steps t < ctx_blocks stream the context band, the last step maps
    onto this q-row's diagonal tile, and that step is skipped when the
    diagonal tile was already accumulated as a context step.
    """
    q_start = i_q * bq
    if ctx_blocks is None:
        k_start = t * bk
        compute = _tile_relevant(q_start, k_start, num_ctx, bq, bk)
        last = num_kb - 1
    else:
        kb = jnp.where(t == ctx_blocks, i_q, t)  # mirrors the kv index_map
        k_start = kb * bk
        compute = jnp.logical_or(t != ctx_blocks, i_q >= ctx_blocks)
        last = ctx_blocks
    return k_start, compute, last


def _banded_grid_specs(h: int, num_qb: int, num_kb: int,
                       ctx_blocks: int | None):
    """(grid, kv_idx) for the forward/dq pallas_calls — the one place
    the (h, num_qb, k-steps) grid and its kv BlockSpec index_map are
    built, so forward and backward can never drift apart."""
    if ctx_blocks is not None:
        grid = (h, num_qb, ctx_blocks + 1)

        def kv_idx(i, j, t):
            # last k-step -> this q-row's diagonal tile; earlier steps
            # walk the context band left-to-right
            return (i, jnp.where(t == ctx_blocks, j, t), 0)
    else:
        grid = (h, num_qb, num_kb)

        def kv_idx(i, j, t):
            return (i, t, 0)

    return grid, kv_idx


# ---------------------------------------------------------------------------
# Forward: online softmax, residualizing (o, lse)
# ---------------------------------------------------------------------------
def _gpo_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                    acc_ref, *, scale: float, num_ctx: int,
                    ctx_blocks: int | None, num_kb: int, bq: int, bk: int):
    """Forward kernel for both grids.

    ``ctx_blocks is None`` — legacy full grid (h, num_qb, num_kb): every
    target×target tile is visited and skipped with @pl.when (O(S^2/b^2)
    grid steps). Otherwise — banded grid (h, num_qb, ctx_blocks + 1);
    k-steps t < ctx_blocks stream the context band and the last step
    (t == ctx_blocks) is mapped by the BlockSpec index_map onto the
    diagonal tile of this q-row; when the diagonal tile already lies
    inside the context band (i_q < ctx_blocks) that step is a duplicate
    visit and only the finalize runs.

    Besides ``o`` the kernel emits the per-row logsumexp ``lse`` — the
    backward residual (DESIGN.md §8) that replaces the (h, S, S)
    probability tensor.
    """
    i_q = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i_q * bq
    k_start, compute, last = _k_step_schedule(
        i_q, t, num_ctx=num_ctx, ctx_blocks=ctx_blocks, num_kb=num_kb,
        bq=bq, bk=bk)

    @pl.when(compute)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        s = jnp.where(_np_tile_mask(q_start, k_start, num_ctx, bq, bk), s,
                      NEG_INF)
        m_prev = m_ref[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot(p.astype(v.dtype), v))
        m_ref[...] = m_new

    @pl.when(t == last)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)


def _gpo_forward(q, k, v, *, num_ctx: int, bq: int, bk: int, interpret: bool,
                 banded: bool):
    """(o (h, s, hd), lse (h, s, 1) f32). ``banded`` must be pre-resolved
    (bq == bk and the band does not saturate the grid).

    Per-row statistics (``lse``, the m/l scratch) carry a trailing unit
    axis: a (bq, 1) block equals the array's last dim and keeps bq on
    the sublane axis, which is what the TPU lowering's (8, 128) block
    rule accepts; a (1, bq) row block over (h, s) is refused."""
    h, s, hd = q.shape
    num_qb, num_kb = s // bq, s // bk
    scale = 1.0 / (hd ** 0.5)
    ctx_blocks = _banded_ctx_blocks(num_ctx, bk, num_kb) if banded else None
    grid, kv_idx = _banded_grid_specs(h, num_qb, num_kb, ctx_blocks)

    def idx(i, j, t):
        return (i, j, 0)

    kernel = functools.partial(_gpo_fwd_kernel, scale=scale, num_ctx=num_ctx,
                               ctx_blocks=ctx_blocks, num_kb=num_kb, bq=bq,
                               bk=bk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), idx),
            pl.BlockSpec((1, bk, hd), kv_idx),
            pl.BlockSpec((1, bk, hd), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, hd), idx),
            pl.BlockSpec((1, bq, 1), idx),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, s, hd), q.dtype),
            jax.ShapeDtypeStruct((h, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
        if not interpret else None,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward: dq on the forward's banded grid; dk/dv on the transposed band
# ---------------------------------------------------------------------------
def _gpo_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, acc_ref, *, scale: float, num_ctx: int,
                       ctx_blocks: int | None, num_kb: int, bq: int, bk: int):
    """dq accumulation over this q-row's k-tiles; same grid and k-step
    schedule (band + diagonal, duplicate-diagonal skip) as the forward.
    Tile scores are recomputed from q/k; probabilities come back from the
    residualized lse (p = exp(s - lse)), never from memory."""
    i_q = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i_q * bq
    k_start, compute, last = _k_step_schedule(
        i_q, t, num_ctx=num_ctx, ctx_blocks=ctx_blocks, num_kb=num_kb,
        bq=bq, bk=bk)

    @pl.when(compute)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        s = jnp.where(_np_tile_mask(q_start, k_start, num_ctx, bq, bk), s,
                      NEG_INF)
        p = jnp.exp(s - lse_ref[0])  # masked entries -> exactly 0
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))  # (bq, bk)
        ds = p * (dp - delta_ref[0]) * scale
        acc_ref[...] = acc_ref[...] + jax.lax.dot(ds, k)

    @pl.when(t == last)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _gpo_bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                         num_ctx: int, ctx_blocks: int | None, num_qb: int,
                         bq: int, bk: int):
    """dk/dv accumulation per k-tile over the q-tiles that attend it.

    The grid's second dimension is the *flattened* transposed band:
    steps t < ctx_blocks*num_qb sweep (k-tile j = t // num_qb,
    q-tile t % num_qb) — context keys are read by every q-row — and the
    remaining num_kb - ctx_blocks steps visit each pure-target k-tile's
    diagonal q-tile only (one step per tile: init, accumulate and
    finalize together). k-tile index is non-decreasing in t, so the
    (bk, hd) accumulators carry across exactly the steps of one k-tile.
    ``ctx_blocks is None`` flattens the full (num_kb, num_qb) grid with
    @pl.when predication instead (the legacy A/B grid)."""
    t = pl.program_id(1)

    if ctx_blocks is None:
        j, iq = t // num_qb, t % num_qb
        first = iq == 0
        last = iq == num_qb - 1
    else:
        band_steps = ctx_blocks * num_qb
        is_band = t < band_steps
        diag = ctx_blocks + t - band_steps
        j = jnp.where(is_band, t // num_qb, diag)
        iq = jnp.where(is_band, t % num_qb, diag)
        first = jnp.logical_or(~is_band, t % num_qb == 0)
        last = jnp.logical_or(~is_band, t % num_qb == num_qb - 1)
    q_start, k_start = iq * bq, j * bk

    @pl.when(first)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _accumulate():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        s = jnp.where(_np_tile_mask(q_start, k_start, num_ctx, bq, bk), s,
                      NEG_INF)
        p = jnp.exp(s - lse_ref[0])  # (bq, bk)
        # dv += p^T do ; ds = p * (dp - delta) ; dk += ds^T q
        dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta_ref[0]) * scale
        dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())))

    if ctx_blocks is None:
        # full grid: predicate away irrelevant (k, q) tiles
        pl.when(_tile_relevant(q_start, k_start, num_ctx, bq, bk))(
            _accumulate)
    else:
        _accumulate()  # every banded step is relevant by construction

    @pl.when(last)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _gpo_backward(q, k, v, do, lse, delta, *, num_ctx: int, bq: int, bk: int,
                  interpret: bool, banded: bool):
    """(dq, dk, dv) via the two banded backward kernels; ``lse`` and
    ``delta`` are (h, s, 1) row statistics (see ``_gpo_forward``)."""
    h, s, hd = q.shape
    num_qb, num_kb = s // bq, s // bk
    scale = 1.0 / (hd ** 0.5)
    ctx_blocks = _banded_ctx_blocks(num_ctx, bk, num_kb) if banded else None

    # ---- dq: the forward's banded grid --------------------------------
    dq_grid, kv_idx = _banded_grid_specs(h, num_qb, num_kb, ctx_blocks)

    def idx(i, j, t):
        return (i, j, 0)

    dq_kernel = functools.partial(
        _gpo_bwd_dq_kernel, scale=scale, num_ctx=num_ctx,
        ctx_blocks=ctx_blocks, num_kb=num_kb, bq=bq, bk=bk)
    dq = pl.pallas_call(
        dq_kernel,
        grid=dq_grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), idx),
            pl.BlockSpec((1, bk, hd), kv_idx),
            pl.BlockSpec((1, bk, hd), kv_idx),
            pl.BlockSpec((1, bq, hd), idx),
            pl.BlockSpec((1, bq, 1), idx),
            pl.BlockSpec((1, bq, 1), idx),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), idx),
        out_shape=jax.ShapeDtypeStruct((h, s, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
        if not interpret else None,
    )(q, k, v, do, lse, delta)

    # ---- dk/dv: the transposed band, flattened ------------------------
    if ctx_blocks is not None:
        steps = ctx_blocks * num_qb + (num_kb - ctx_blocks)

        def decode(t):
            band_steps = ctx_blocks * num_qb
            diag = ctx_blocks + t - band_steps
            j = jnp.where(t < band_steps, t // num_qb, diag)
            iq = jnp.where(t < band_steps, t % num_qb, diag)
            return j, iq
    else:
        steps = num_kb * num_qb

        def decode(t):
            return t // num_qb, t % num_qb

    def t_q_idx(i, t):
        return (i, decode(t)[1], 0)

    def t_kv_idx(i, t):
        return (i, decode(t)[0], 0)

    dkdv_kernel = functools.partial(
        _gpo_bwd_dkdv_kernel, scale=scale, num_ctx=num_ctx,
        ctx_blocks=ctx_blocks, num_qb=num_qb, bq=bq, bk=bk)
    dk, dv = pl.pallas_call(
        dkdv_kernel,
        grid=(h, steps),
        in_specs=[
            pl.BlockSpec((1, bq, hd), t_q_idx),
            pl.BlockSpec((1, bk, hd), t_kv_idx),
            pl.BlockSpec((1, bk, hd), t_kv_idx),
            pl.BlockSpec((1, bq, hd), t_q_idx),
            pl.BlockSpec((1, bq, 1), t_q_idx),
            pl.BlockSpec((1, bq, 1), t_q_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, hd), t_kv_idx),
            pl.BlockSpec((1, bk, hd), t_kv_idx),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, s, hd), k.dtype),
            jax.ShapeDtypeStruct((h, s, hd), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, hd), jnp.float32),
            pltpu.VMEM((bk, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
        if not interpret else None,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wiring + grid accounting
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _gpo_attention(q, k, v, num_ctx, bq, bk, interpret, banded):
    o, _ = _gpo_forward(q, k, v, num_ctx=num_ctx, bq=bq, bk=bk,
                        interpret=interpret, banded=banded)
    return o


def _gpo_attention_fwd(q, k, v, num_ctx, bq, bk, interpret, banded):
    o, lse = _gpo_forward(q, k, v, num_ctx=num_ctx, bq=bq, bk=bk,
                          interpret=interpret, banded=banded)
    return o, (q, k, v, o, lse)


def _gpo_attention_bwd(num_ctx, bq, bk, interpret, banded, res, do):
    q, k, v, o, lse = res
    # preprocessing pass: delta_i = sum_d do_id * o_id = sum_j p_ij dp_ij,
    # the softmax-jacobian row term shared by every tile of row i
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)
    return _gpo_backward(q, k, v, do.astype(q.dtype), lse, delta,
                         num_ctx=num_ctx, bq=bq, bk=bk, interpret=interpret,
                         banded=banded)


_gpo_attention.defvjp(_gpo_attention_fwd, _gpo_attention_bwd)


def _banded_ctx_blocks(num_ctx: int, bk: int, num_kb: int) -> int | None:
    """k-blocks of the context band, or None when the band saturates the
    grid (banded would add a duplicate diagonal step per q-row, so the
    full grid is used instead). Single source of truth for the kernel
    wrappers and gpo_tile_counts."""
    ctx_blocks = min(-(-num_ctx // bk), num_kb)
    return ctx_blocks if ctx_blocks < num_kb else None


def gpo_tile_counts(s: int, num_ctx: int, bq: int, bk: int) -> tuple[int, int]:
    """(banded_tiles, full_grid_tiles) per head for a given shape —
    the grid-level work ratio of the banded forward kernel."""
    num_qb, num_kb = s // bq, s // bk
    ctx_blocks = _banded_ctx_blocks(num_ctx, bk, num_kb)
    banded = num_qb * (ctx_blocks + 1 if ctx_blocks is not None else num_kb)
    return banded, num_qb * num_kb


def gpo_tile_counts_bwd(s: int, num_ctx: int, bq: int,
                        bk: int) -> tuple[int, int]:
    """(banded_bwd_tiles, full_grid_bwd_tiles) per head: dq grid steps
    plus dk/dv grid steps — the backward-pass analogue of
    ``gpo_tile_counts``."""
    num_qb, num_kb = s // bq, s // bk
    ctx_blocks = _banded_ctx_blocks(num_ctx, bk, num_kb)
    full = 2 * num_qb * num_kb
    if ctx_blocks is None:
        return full, full
    dq = num_qb * (ctx_blocks + 1)
    dkdv = ctx_blocks * num_qb + (num_kb - ctx_blocks)
    return dq + dkdv, full


def gpo_attention_hsd(q, k, v, *, num_ctx: int, bq: int = 128, bk: int = 128,
                      interpret: bool | None = None, banded: bool = True):
    """q, k, v (H, S, hd) -> (H, S, hd) with the neural-process mask.

    Differentiable: a flash-style custom VJP keeps ``jax.grad`` on the
    same banded grid (DESIGN.md §8) — both round engines train through
    this kernel when ``GPOConfig.use_pallas_attention`` is set.

    S must be a multiple of the block sizes (ops.gpo_attention pads). The
    banded grid requires bq == bk (the wrapper falls back to the full
    grid otherwise). ``interpret`` defaults to the backend (interpret on
    CPU, native on TPU) so direct callers never silently run interpret
    mode on hardware.
    """
    if interpret is None:
        interpret = interpret_default()
    h, s, hd = q.shape
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    if banded:
        assert bq == bk, "banded grid requires square tiles"
        # resolve the saturated-band fallback HERE so the forward and
        # backward pallas_calls agree on the grid for this shape
        banded = _banded_ctx_blocks(num_ctx, bk, s // bk) is not None
    return _gpo_attention(q, k, v, num_ctx, bq, bk, bool(interpret), banded)
