"""Event-loop time of fan-out (``fanout_busy_ns``: each publish's
``_fan_out`` and the joined writes at its slice's end) per frame handed
to a subscriber's socket, between the traced slice's two snapshots, in
microseconds: what one delivery costs the loop. Nothing where the
snapshots lack the count or no frame was delivered between them."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or "deliveries" not in sl.a or "deliveries" not in sl.b:
        return None
    deliveries = program_spans.delta(sl, "deliveries")
    if not deliveries:
        return None
    return program_spans.delta(sl, "fanout_busy_ns") / deliveries / 1e3
