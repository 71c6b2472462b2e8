"""Event-loop time of a read's frame loop per PUBACK frame in it
(``ack_busy_ns`` / ``ack_n``: the scans that held PUBACK frames and no
publish, taken by an ack run or a frame at a time), between the traced
slice's two snapshots, in microseconds: what one acknowledgement of a
delivery costs the loop. Nothing where the snapshots lack the count or no
such frame came in between them."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or "ack_n" not in sl.a or "ack_n" not in sl.b:
        return None
    acks = program_spans.delta(sl, "ack_n")
    if not acks:
        return None
    return program_spans.delta(sl, "ack_busy_ns") / acks / 1e3
