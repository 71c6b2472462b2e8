"""Frames handed to subscribers' sockets (``deliveries``) per read of a
socket's readiness by fan-out (``socket_checks``: closed, TLS, the
transport's buffer, the outbound queue), between the traced slice's two
snapshots. 1.0 where every delivery reads its socket
(``Server._flush_variant``: a socket hit once a slice, written at its
own publish); as many as a completion slice sends one socket where the
slice reads each socket it corked once and keeps what it read
(``clients.SliceSocket``): 64 for an echoed 64-frame chunk, 20 where a
plant's 256-publish slice goes to some 50 sockets. Deliveries that read
no socket at all (the per-subscriber path behind an observing hook or a
v5 alias) are in the numerator alone. A program whose snapshots lack
the count gives nothing, as does a slice in which no socket was read."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or any(
        k not in snap for k in ("deliveries", "socket_checks") for snap in (sl.a, sl.b)
    ):
        return None
    checks = program_spans.delta(sl, "socket_checks")
    if not checks:
        return None
    return program_spans.delta(sl, "deliveries") / checks
