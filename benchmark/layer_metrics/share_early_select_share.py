"""Share of the shared groups selected that a completion slice picked in
its prologue, before it read its target sockets (``share_groups_early`` /
``share_groups``: ``Server._complete_staged``), between the traced
slice's two snapshots, in percent. 100 where no hook selects and no
predicate rule is live; 0 where the pick waits for each publish's turn. A
program whose snapshots lack the counts gives nothing, as does a slice in
which no group was selected."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or any(
        k not in snap
        for k in ("share_groups_early", "share_groups")
        for snap in (sl.a, sl.b)
    ):
        return None
    groups = program_spans.delta(sl, "share_groups")
    if not groups:
        return None
    return 100.0 * program_spans.delta(sl, "share_groups_early") / groups
