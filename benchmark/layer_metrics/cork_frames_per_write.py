"""Packets a written cork held (``cork_frames`` / ``cork_writes``:
``Client._write`` / ``_uncork``), between the traced slice's two
snapshots: what one transport write of a cork carried — a read's acks,
or a completion slice's deliveries to one socket. A host application
that takes every publish of a plant holds a slice's 256 in one cork; a
socket hit twice a slice holds 2. A program whose snapshots lack the
counts gives nothing, as does a slice in which no cork was written."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or any(
        k not in snap for k in ("cork_frames", "cork_writes") for snap in (sl.a, sl.b)
    ):
        return None
    writes = program_spans.delta(sl, "cork_writes")
    if not writes:
        return None
    return program_spans.delta(sl, "cork_frames") / writes
