"""Open loop: a percentile of the window's publish-to-receive delays,
named by the metric's suffix (``delay_tail_ms.p90`` is the 90th): a
steadier statistic beside the end-to-end tail."""

import statistics


def read(ctx):
    delays = ctx["delays_ns"]
    if len(delays) < 100:
        return None
    percentile = int(ctx["metric"].rsplit(".p", 1)[1])
    cuts = statistics.quantiles(delays, n=100, method="inclusive")
    return cuts[percentile - 1] / 1e6
