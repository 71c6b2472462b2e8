"""QoS>0 deliveries a completion slice's run settled per run, between
the traced slice's two snapshots (``qos_run_entries`` / ``qos_runs``:
``Server._settle_run``, one ``Inflight.begin_run`` a socket a slice).
The deliveries to one socket that share a hold of its session's inflight
lock. A program whose snapshots lack the counts gives nothing, as does a
slice in which no run was settled."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or any(
        k not in snap
        for k in ("qos_run_entries", "qos_runs")
        for snap in (sl.a, sl.b)
    ):
        return None
    runs = program_spans.delta(sl, "qos_runs")
    if not runs:
        return None
    return program_spans.delta(sl, "qos_run_entries") / runs
