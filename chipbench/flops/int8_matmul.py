"""Work of the weight-only int8 matmul kernel (``_int8_matmul_kernel``),
counted from each call's shapes.

A call multiplies x (M, K) f32 by q (K, N) int8 and scales each output
column. The kernel upcasts the int8 tile and runs an f32 dot on the MXU,
so its compute peak is the chip's bf16 peak, not its int8 peak. Its
operands reach it in VMEM: XLA places them there ahead of the call
(``S(1)`` in the compiled HLO; the HBM reads are separate asynchronous
copies in the trace), so the kernel's own time holds no HBM traffic, and
at these shapes VMEM bandwidth (18 TB/s) moves its bytes in less time
than the MXU needs for its FLOPs. The least time of a call is therefore
its FLOPs over the bf16 peak. Shapes are padded as the kernel's wrapper
pads them: M to its row block (at most 128, at least 8), K to a multiple
of 8, N to its column block.
"""
from __future__ import annotations


def _pad(n: int, block: int) -> int:
    return -(-n // block) * block


def call(batch: int, m: int, k: int, n: int) -> float:
    """FLOPs of one call on ``batch`` examples of x (m, k)."""
    bm, bn = min(128, max(8, m)), min(128, max(8, n))
    return 2.0 * batch * _pad(m, bm) * _pad(k, 8) * _pad(n, bn)


def gpo_pass(model: dict, batch: int, rows: int, *, head: bool) -> float:
    """FLOPs of all kernel calls of one pass of the predictor over
    ``batch`` examples of ``rows`` tokens: the input projection, every
    block's six dense weights, and the head when the pass has one."""
    e, d, f = model["d_embed"], model["d_model"], model["d_ff"]
    shapes = [(e + 2, d)] + model["num_layers"] * [
        (d, d), (d, d), (d, d), (d, d), (d, f), (f, d)]
    if head:
        shapes.append((d, 2 if model["learn_sigma"] else 1))
    return sum(call(batch, rows, k, n) for k, n in shapes)
