"""Model FLOP utilization of the frozen backbone: the model FLOPs
(``flops/granite_h.py``) of the real tokens of the texts the traced
window embedded, over its length, as a share of the chip's bf16 peak.
Padding tokens, the predictor, the pool and the norms are not counted."""


def read(ctx):
    flops = ctx.counters.get("backbone_flops")
    if not flops:
        return None
    return 100.0 * flops / ctx.counters["window_s"] / ctx.peaks["bf16_flops"]
