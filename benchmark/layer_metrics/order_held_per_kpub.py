"""Fallbacks that joined their publisher's order as held members
(``MatchStage.order_held``: walked on the host at once, completed in
their place) per 1,000 topics the matcher took in, between the traced
slice's two snapshots. What the order guarantee is asked to carry in the
cell; a program whose snapshots lack the count gives nothing."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or "order_held" not in sl.a or "order_held" not in sl.b:
        return None
    topics = program_spans.delta(sl, "topics")
    if not topics:
        return None
    return 1000.0 * program_spans.delta(sl, "order_held") / topics
