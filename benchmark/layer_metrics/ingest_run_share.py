"""Share of the publishes the read loops took in that an ingest run took
(one call a run of a scan's PUBLISH frames, ``Server.ingest_run``), the
rest having gone a frame at a time, between the traced slice's two
snapshots, in percent. A program whose snapshots lack the count gives
nothing."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    key = "ingest_run_publishes"
    if sl is None or key not in sl.a or key not in sl.b:
        return None
    taken_in = program_spans.delta(sl, "ingest_n")
    if not taken_in:
        return None
    return 100.0 * program_spans.delta(sl, key) / taken_in
