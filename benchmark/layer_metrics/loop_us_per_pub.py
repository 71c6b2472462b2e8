"""Event-loop time a publish costs before its batch (``ingest``: frame
scanned to ``submit()``) and after it (``fanout``: fan-out start to flush
done), from the program's own per-publish counters over the traced slice
(``program_spans.loop_ns_per_pub``)."""

import program_spans


def read(ctx):
    return program_spans.read_part(ctx, program_spans.loop_ns_per_pub, 1e3)
