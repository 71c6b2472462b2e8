"""The share of fan-out's loop time that is the joined writes at the
completion slices' ends (``slice_flush_ns`` / ``fanout_busy_ns``, which
holds them), between the traced slice's two snapshots, in percent: the
sends' half of a slice, against a delivery's Python. Nothing where the
snapshots lack the span or no publish fanned out between them."""

import loop_ledger


def read(ctx):
    sl = loop_ledger.load("slice_flush_ns", "fanout_busy_ns")
    if sl is None:
        return None
    busy = loop_ledger.delta(sl, "fanout_busy_ns")
    if not busy:
        return None
    return 100.0 * loop_ledger.delta(sl, "slice_flush_ns") / busy
