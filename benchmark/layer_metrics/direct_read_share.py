"""Share of the wake-ups of a connection's frame scan on data
(``socket_reads``) that the broker's own protocol took in inside the
transport's read callback (``direct_reads``: ``clients._DirectFeed``),
the rest having come through an asyncio stream reader (a future, two task
steps and a timer a read), between the traced slice's two snapshots, in
percent. A program whose snapshots lack either count gives nothing."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or any(
        key not in snap
        for key in ("direct_reads", "socket_reads") for snap in (sl.a, sl.b)
    ):
        return None
    reads = program_spans.delta(sl, "socket_reads")
    if not reads:
        return None
    return 100.0 * program_spans.delta(sl, "direct_reads") / reads
