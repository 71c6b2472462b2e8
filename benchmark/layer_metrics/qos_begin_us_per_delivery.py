"""Event-loop time of the encode-once fan-out's QoS>0 bookkeeping per
delivery begun, between the traced slice's two snapshots, in
microseconds: ``qos_begin_ns`` (armed only: the inflight entry's clone,
``Inflight.begin``, the ``on_qos_publish`` hooks where one provides it)
over ``qos_begins`` (``Server._flush_variant``). A program whose
snapshots lack the counts gives nothing, as does a slice in which no
QoS>0 delivery was begun."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or any(
        k not in snap for k in ("qos_begin_ns", "qos_begins") for snap in (sl.a, sl.b)
    ):
        return None
    begins = program_spans.delta(sl, "qos_begins")
    if not begins:
        return None
    return program_spans.delta(sl, "qos_begin_ns") / begins / 1e3
