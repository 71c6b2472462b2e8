"""Share of the corks written that a cork past its byte bound forced
before its opener closed it (``cork_early_writes`` / ``cork_writes``;
``clients.CORK_MAX_BYTES``), between the traced slice's two snapshots,
in percent: how often one socket's share of a completion slice outgrows
one write. A program whose snapshots lack the counts gives nothing, as
does a slice in which no cork was written."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or any(
        k not in snap
        for k in ("cork_early_writes", "cork_writes") for snap in (sl.a, sl.b)
    ):
        return None
    writes = program_spans.delta(sl, "cork_writes")
    if not writes:
        return None
    return 100.0 * program_spans.delta(sl, "cork_early_writes") / writes
