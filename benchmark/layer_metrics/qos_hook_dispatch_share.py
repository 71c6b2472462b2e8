"""Share of the encode-once fan-out's QoS>0 deliveries that dispatched
``on_qos_publish`` (``qos_begins_hooked`` / ``qos_begins``:
``Server._flush_variant``), between the traced slice's two snapshots, in
percent. 0 where no attached hook provides it (the dispatch is skipped),
100 with a storage or debug hook. A program whose snapshots lack the
counts gives nothing, as does a slice in which no QoS>0 delivery was
begun."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or any(
        k not in snap
        for k in ("qos_begins_hooked", "qos_begins")
        for snap in (sl.a, sl.b)
    ):
        return None
    begins = program_spans.delta(sl, "qos_begins")
    if not begins:
        return None
    return 100.0 * program_spans.delta(sl, "qos_begins_hooked") / begins
