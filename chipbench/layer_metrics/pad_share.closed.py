"""Share of the token rows the serving engine computed that were padding:
batches padded to a batch bucket, targets to a target bucket, and
prefilled contexts to a context bucket, from the ``BatchRecord`` log and
each request's lengths."""


def read(ctx):
    c = ctx.counters
    if not c["points_computed"]:  # nothing computed, or not counted
        return None
    return 100.0 * (1.0 - c["points_useful"] / c["points_computed"])
