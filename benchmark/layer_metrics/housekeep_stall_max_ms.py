"""The longest of the traced slice's housekeeping ticks, in milliseconds:
the ``mqtt/housekeep`` spans the program kept while the session was live
(``DeviceProfiler.note_host_span``: one a tick, around its expiry and
eviction passes; the tick's $SYS publish is a span of its own,
``mqtt/sys.publish``, and not in it). A program that keeps no such spans
gives nothing, as does a slice that held no tick."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None:
        return None
    held = [
        t1 - t0 for name, t0, t1, _args in sl.b.get("host_spans", ())
        if name == "mqtt/housekeep"
    ]
    return max(held) / 1e6 if held else None
