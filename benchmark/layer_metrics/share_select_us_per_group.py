"""Event-loop time of shared-group selection per group selected, between
the traced slice's two snapshots, in microseconds: ``share_select_ns``
(armed only: the forced materialization of a lazy result that holds
groups, the hooks' pick, ``select_shared``, ``merge_shared_selected``)
over ``share_groups`` (``Server._select_shared``). A program whose
snapshots lack the counts gives nothing, as does a slice in which no
group was selected."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or any(
        k not in snap for k in ("share_select_ns", "share_groups") for snap in (sl.a, sl.b)
    ):
        return None
    groups = program_spans.delta(sl, "share_groups")
    if not groups:
        return None
    return program_spans.delta(sl, "share_select_ns") / groups / 1e3
