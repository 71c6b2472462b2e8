"""Calls that reached a client's socket (a transport write, or one send
of the native fan-out flush) per topic the matcher took in, between the
traced slice's two snapshots: acks, deliveries and everything else the
broker wrote. A program whose snapshots lack the count gives nothing."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or "socket_sends" not in sl.a or "socket_sends" not in sl.b:
        return None
    topics = program_spans.delta(sl, "topics")
    if not topics:
        return None
    return program_spans.delta(sl, "socket_sends") / topics
