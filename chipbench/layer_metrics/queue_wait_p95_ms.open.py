"""95th percentile of the time a request waited from its due time to the
start of the ``step`` that took it, over the traced window. The tracer
slows the host loop, so the queue it reads is longer than an untraced
window's at the same rate."""

from chipbench import common


def read(ctx):
    waits = ctx.counters["queue_wait_ms"]
    return common.percentile(waits, 95) if waits else None
