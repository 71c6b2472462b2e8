"""Frames handed to subscribers' sockets (``deliveries``: the telemetry
plane's ``fanout_deliveries``, ``$SYS`` left out) per publish fanned out
(``fanout_n``), between the traced slice's two snapshots: 0.1 where
nearly every matched subscriber is offline, 1,000 where a thousand live
sockets hear every topic. Both counts move inside ``_fan_out``, one
publish at a time, so a snapshot that falls inside a batch's fan-out
cuts both at the same publish. (``topics``, which the matcher counts
when a batch RESOLVES, would not do: where one batch's fan-out takes a
second, a 3 s slice holds one or two resolves and anything between one
and two and a half fan-outs.) ``fanout_n`` counts while the program is
armed, which lags the session by up to one sampler period (35 ms): a
fan-out that begins inside that lag is in ``deliveries`` alone. A
program whose snapshots lack the counts gives nothing."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or "deliveries" not in sl.a or "deliveries" not in sl.b:
        return None
    fanned_out = program_spans.delta(sl, "fanout_n")
    if not fanned_out:
        return None
    return program_spans.delta(sl, "deliveries") / fanned_out
