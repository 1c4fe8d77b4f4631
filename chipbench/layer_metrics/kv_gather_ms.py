"""Time one serving step spends building the decode's inputs:
``serve.gather`` (the cached K/V padded to the batch's context bucket,
the padding rows, the stacks and the target packing, with their
transfers) over the number of ``serve.step`` spans, from the program's
own spans."""

from chipbench import program_spans as ps


def read(ctx):
    rec = ps.recorded()
    if rec is None:
        return None
    return ps.per(rec, ps.seconds(rec, "serve.gather"), "serve.step")
