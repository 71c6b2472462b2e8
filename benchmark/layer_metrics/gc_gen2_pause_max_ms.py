"""The longest full (generation-2) collection among the newest 64 the
process ran before the traced slice's second snapshot (``gc2_recent``,
from a hook that is always on: set-up's collections over the loaded heap
are among them); 0 where it ran none. A full collection stops the whole
broker for its length, and its length follows the count of objects the
collector tracks. A program whose snapshots lack the list gives nothing."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or "gc2_recent" not in sl.b:
        return None
    return max((length for _end, length in sl.b["gc2_recent"]), default=0) / 1e6
