"""Roofline share of the int8 weight-only matmul kernel: the least time
its calls could take on the chip over the device time of the kernel's
events in the trace. The kernel runs an f32 dot on upcast weights with
its operands already in VMEM, so the bound is the MXU at the chip's bf16
peak (``flops/int8_matmul.py``). The FLOPs are those of the shapes the
kernel is handed, padding included (rows to its row block, the head's
one column to 8): the share is of the work the kernel was given, and the
serving engine's own padding is ``pad_share.closed``'s."""

KERNEL = "int8_matmul"


def read(ctx):
    seconds = ctx.trace.kernel_seconds(KERNEL)
    flops = ctx.counters["int8_flops"]
    if seconds <= 0 or not flops:
        return None
    return 100.0 * flops / ctx.peaks["bf16_flops"] / seconds
