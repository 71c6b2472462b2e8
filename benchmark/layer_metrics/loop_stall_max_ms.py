"""The longest the broker's event loop was held during the traced slice:
how late the latest 5 ms heartbeat ran (``program_spans.loop_stall_max_ns``)."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None:
        return None
    ns = program_spans.loop_stall_max_ns(sl)
    return None if ns is None else ns / 1e6
