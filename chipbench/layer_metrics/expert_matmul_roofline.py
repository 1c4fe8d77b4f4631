"""Roofline share of the grouped expert products (``jax.lax.ragged_dot``,
the TPU's ``ragged-dot`` custom call) of the backbone's dropless MoE: the
least time their FLOPs could take at the chip's bf16 peak over the
device time of their events in the trace. The FLOPs are those of the
(token, expert) pairs the products were handed (``flops/granite_h.py``,
from the server's own count), padding tokens included: the share is of
the work the products were given. The call that computes each product's
group metadata (``ragged-dot-metadata``) is not a product and is left
out."""

KERNEL = "ragged-dot"
METADATA = "ragged-dot-metadata"


def read(ctx):
    seconds = sum(v for k, v in ctx.trace.op_seconds.items()
                  if KERNEL in k and METADATA not in k)
    flops = ctx.counters.get("expert_flops")
    if seconds <= 0 or not flops:
        return None
    return 100.0 * flops / ctx.peaks["bf16_flops"] / seconds
