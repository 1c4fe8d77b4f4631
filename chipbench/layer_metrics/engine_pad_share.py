"""Share of the token rows the serving engine computed that were padding,
by the engine's own count: over its ``serve.prefill`` and
``serve.decode`` spans, 1 - real rows (``rows``) / rows computed
(``computed``). ``pad_share.closed`` derives the same from outside the
program."""

from chipbench import program_spans as ps

PHASES = ("serve.prefill", "serve.decode")


def read(ctx):
    rec = ps.recorded()
    if rec is None:
        return None
    computed = sum(ps.counted(rec, n, "computed") for n in PHASES)
    if not computed:
        return None
    rows = sum(ps.counted(rec, n, "rows") for n in PHASES)
    return 100.0 * (1.0 - rows / computed)
