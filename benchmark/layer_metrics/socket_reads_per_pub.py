"""Wake-ups of a connection's read loop on data (``socket_reads``: one
``recv`` each; the stream may join two) per topic the matcher took in,
between the traced slice's two snapshots: publishers' frames, subscribers'
acks and everything else the broker read. A program whose snapshots lack
the count gives nothing. Divides by the topics the matcher took in:
where a slice holds a few batches of 40 it reads in steps."""

import loop_ledger


def read(ctx):
    sl = loop_ledger.load("socket_reads")
    if sl is None:
        return None
    topics = loop_ledger.delta(sl, "topics")
    if not topics:
        return None
    return loop_ledger.delta(sl, "socket_reads") / topics
