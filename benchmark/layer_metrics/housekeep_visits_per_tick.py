"""Sessions the housekeeping tick's expiry and eviction passes looked at,
per tick, between the traced slice's two snapshots: ``housekeep_visits``
over ``housekeep_ticks`` (``Server.housekeep``: the sessions holding
in-flight entries, the disconnected ones awaiting expiry, those with a
transport or queue backlog; a loop shard's own eviction sweep adds its
visits and no tick). A program whose snapshots lack the counts gives
nothing, as does a slice that held no tick."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or any(
        key not in snap
        for key in ("housekeep_visits", "housekeep_ticks") for snap in (sl.a, sl.b)
    ):
        return None
    ticks = program_spans.delta(sl, "housekeep_ticks")
    if not ticks:
        return None
    return program_spans.delta(sl, "housekeep_visits") / ticks
