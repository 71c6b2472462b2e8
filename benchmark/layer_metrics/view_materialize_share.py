"""Lazy match results forced into dicts per publish fanned out
(``view_materializations``, the C view module's own count, over
``fanout_n``), between the traced slice's two snapshots, in percent: the
share of publishes that left the zero-materialization fan-out, as a
result that holds shared groups must. A program whose snapshots lack the
count gives nothing, as does a slice in which nothing was fanned out."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or any(
        "view_materializations" not in snap for snap in (sl.a, sl.b)
    ):
        return None
    publishes = program_spans.delta(sl, "fanout_n")
    if not publishes:
        return None
    return 100.0 * program_spans.delta(sl, "view_materializations") / publishes
