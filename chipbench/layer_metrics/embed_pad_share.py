"""Share of the tokens the serving engine ran through the backbone that
were padding, by the engine's own count: over its ``serve.embed`` spans,
1 - real tokens (``tokens``) / tokens computed (``computed``: texts padded
to their length bucket, groups to their row bucket)."""

from chipbench import program_spans as ps


def read(ctx):
    rec = ps.recorded()
    if rec is None:
        return None
    computed = ps.counted(rec, "serve.embed", "computed")
    if not computed:
        return None
    return 100.0 * (1.0 - ps.counted(rec, "serve.embed", "tokens") / computed)
