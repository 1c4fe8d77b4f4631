"""Time one serving step waits for the device: ``serve.wait`` (the
decode's ``block_until_ready`` and the copy of its rows to the host)
over the number of ``serve.step`` spans, from the program's own spans."""

from chipbench import program_spans as ps


def read(ctx):
    rec = ps.recorded()
    if rec is None:
        return None
    return ps.per(rec, ps.seconds(rec, "serve.wait"), "serve.step")
