"""What one call that reached a socket cost the event loop in this run,
on this host: ``send_busy_ns`` (clock reads around the transport writes
and the native fan-out flush, the calls that count ``socket_sends``) over
``socket_sends``, between the traced slice's two snapshots, in
microseconds. Nothing where the snapshots lack the span or no send was
made between them."""

import loop_ledger


def read(ctx):
    sl = loop_ledger.load("send_busy_ns", "socket_sends")
    if sl is None:
        return None
    sends = loop_ledger.delta(sl, "socket_sends")
    if not sends:
        return None
    return loop_ledger.delta(sl, "send_busy_ns") / sends / 1e3
