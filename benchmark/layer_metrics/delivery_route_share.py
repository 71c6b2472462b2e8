"""Share of the deliveries of the encode-once fan-out that left by each
of its three ways (``Server._flush_variant``), between the traced
slice's two snapshots, in percent of the three together:
``delivery_route_share.flush`` the native flush (a ready socket: idle
transport, empty outbound queue), ``.cork`` an open cork (the completion
slice targets the socket again, or its own read is in hand: one write a
socket a slice), ``.queue`` the bounded outbound queue (a socket with a
backlog: one write a frame, by its write loop). The three come to 100.
A program whose snapshots lack the counts gives nothing, as does a slice
in which nothing was delivered."""

import program_spans

ROUTES = ("flush", "cork", "queue")


def read(ctx):
    sl = program_spans.load()
    keys = ["deliveries_" + r for r in ROUTES]
    if sl is None or any(k not in snap for k in keys for snap in (sl.a, sl.b)):
        return None
    total = sum(program_spans.delta(sl, k) for k in keys)
    if not total:
        return None
    route = ctx["metric"].split(".")[1]
    return 100.0 * program_spans.delta(sl, "deliveries_" + route) / total
