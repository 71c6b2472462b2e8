"""Ready sockets a ``select()`` of the event loop returned
(``poll_ready_n`` over ``poll0_n`` + ``pollw_n``), between the traced
slice's two snapshots: how many connections one turn of the loop serves,
which is what a system call a turn is shared over. Beside
``loop_polls_per_pub``. A program whose snapshots lack the loop's ledger
gives nothing."""

import loop_ledger


def read(ctx):
    sl = loop_ledger.load("poll0_n", "pollw_n", "poll_ready_n")
    if sl is None:
        return None
    polls = loop_ledger.delta(sl, "poll0_n") + loop_ledger.delta(sl, "pollw_n")
    if not polls:
        return None
    return loop_ledger.delta(sl, "poll_ready_n") / polls
