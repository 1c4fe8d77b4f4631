"""Model FLOP utilization: the model FLOPs (``flops/gpo.py``) of the work
the traced window completed, over its length, as a share of the chip's
bf16 peak. ``mfu.train`` counts the predictor's forward and backward of
every client-epoch (the optimizer, the aggregation and the evaluation are
not model FLOPs); ``mfu.serve`` the prefill and decode of the requests'
real rows, padding left out."""


def read(ctx):
    c = ctx.counters
    return 100.0 * c["model_flops"] / c["window_s"] / ctx.peaks["bf16_flops"]
