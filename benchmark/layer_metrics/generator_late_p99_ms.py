"""Open loop: how late the generator sent, actual send minus due time,
99th percentile over the window's sends."""

import statistics


def read(ctx):
    late = ctx["late_ns"]
    if len(late) < 100:
        return None
    return statistics.quantiles(late, n=100, method="inclusive")[98] / 1e6
