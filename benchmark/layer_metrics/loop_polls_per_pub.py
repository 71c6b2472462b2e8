"""``select()`` calls of the event loop (those that could block and those
that could not) per topic the matcher took in, between the traced slice's
two snapshots: one count of system calls a publish by kind, beside
``socket_reads_per_pub`` and ``socket_sends_per_pub``. No heartbeat runs
through a loop whose selector is framed, so they are the broker's own. A
program whose snapshots lack the loop's ledger gives nothing. Divides by the
topics the matcher took in: where a slice holds a few batches of 40 it reads in steps."""

import loop_ledger


def read(ctx):
    sl = loop_ledger.load("poll0_n", "pollw_n")
    if sl is None:
        return None
    topics = loop_ledger.delta(sl, "topics")
    if not topics:
        return None
    return (loop_ledger.delta(sl, "poll0_n") + loop_ledger.delta(sl, "pollw_n")) / topics
