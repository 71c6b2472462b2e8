"""Share of the PUBACK frames the read loops took in that an ack run took
(one call a stretch of a scan's bare PUBACK frames, ``Server.ack_run``),
the rest having gone a frame at a time, between the traced slice's two
snapshots, in percent. The frames are those of scans that held no
publish (``ack_n``: a subscriber's reads). A program whose snapshots lack
either count gives nothing."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or any(
        key not in snap
        for key in ("ack_run_acks", "ack_n") for snap in (sl.a, sl.b)
    ):
        return None
    acks = program_spans.delta(sl, "ack_n")
    if not acks:
        return None
    return 100.0 * program_spans.delta(sl, "ack_run_acks") / acks
