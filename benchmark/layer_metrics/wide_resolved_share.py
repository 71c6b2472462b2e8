"""Share of the topics the matcher took in whose DEVICE answer, served as
it came, held the hit of a wide entry (a filter that more subscribers
hold than the table's window, laid over consecutive ordinals:
``MatcherStats.wide_topics``), between the traced slice's two snapshots,
in percent. A program whose snapshots lack the count (one that walks
such filters on the host) gives nothing."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or "wide_topics" not in sl.a or "wide_topics" not in sl.b:
        return None
    topics = program_spans.delta(sl, "topics")
    if not topics:
        return None
    return 100.0 * program_spans.delta(sl, "wide_topics") / topics
