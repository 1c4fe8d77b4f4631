"""Host time of one serving step: the mean of ``serve.step`` less its
``serve.wait`` (the blocking wait for the decode and the copy of its
rows), from the program's own spans. It holds admission, prefill
dispatch, the decode inputs' assembly and transfers, the decode
dispatch and the completion records."""

from chipbench import program_spans as ps


def read(ctx):
    rec = ps.recorded()
    if rec is None:
        return None
    return ps.per(rec, ps.seconds(rec, "serve.step")
                  - ps.seconds(rec, "serve.wait"), "serve.step")
