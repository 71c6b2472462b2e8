"""Share of the encode-once fan-out's QoS>0 deliveries whose inflight
bookkeeping a completion slice's run settled (``qos_run_entries`` /
``qos_begins``: ``Server._flush_variant``, ``Server._settle_run``),
between the traced slice's two snapshots, in percent. 100 where every
QoS>0 delivery went to a socket its slice kept a record of and no hook
provides ``on_qos_publish``; the rest were begun one at a time. A
program whose snapshots lack the counts gives nothing, as does a slice
in which no QoS>0 delivery was begun."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or any(
        k not in snap
        for k in ("qos_run_entries", "qos_begins")
        for snap in (sl.a, sl.b)
    ):
        return None
    begins = program_spans.delta(sl, "qos_begins")
    if not begins:
        return None
    return 100.0 * program_spans.delta(sl, "qos_run_entries") / begins
