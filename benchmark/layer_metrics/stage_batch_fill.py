"""Topics per device batch over the window (``MatcherStats.topics`` over
``MatcherStats.batches``)."""


def read(ctx):
    c = ctx["counters"]
    return c["topics"] / c["batches"] if c["batches"] else None
