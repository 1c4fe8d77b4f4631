"""Host time of the round driver per fused block: the time inside
``fed.run`` less its ``fed.fetch`` spans (where the host waits for the
block's losses and scores), over the number of ``fed.block`` spans,
from the program's own spans. It holds the blocks' dispatch, the
``History`` records, the evaluation metrics and the log lines."""

from chipbench import program_spans as ps


def read(ctx):
    rec = ps.recorded()
    if rec is None:
        return None
    return ps.per(rec, ps.seconds(rec, "fed.run")
                  - ps.seconds(rec, "fed.fetch"), "fed.block")
