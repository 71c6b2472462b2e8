"""Share of the shared groups selected whose chosen member had no
connected client (``share_picks_offline`` / ``share_groups``:
``Server._select_shared``), between the traced slice's two snapshots, in
percent: the copies mochi's first-member rule sends to an offline
session, which reach no socket. A program whose snapshots lack the
counts gives nothing, as does a slice in which no group was selected."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or any(
        k not in snap
        for k in ("share_picks_offline", "share_groups")
        for snap in (sl.a, sl.b)
    ):
        return None
    groups = program_spans.delta(sl, "share_groups")
    if not groups:
        return None
    return 100.0 * program_spans.delta(sl, "share_picks_offline") / groups
