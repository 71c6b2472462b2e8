"""Share of the window's publishes answered from device results: all but
the host-routed topics (build-saturated buckets, overflows), the staging
fallbacks of every class and the topics the breaker sent to the host.
Topics the wildcard-free exact map answered (``host_fast``) are not
device-resolved either."""


def read(ctx):
    c = ctx["counters"]
    taken = c["topics"] + c["stage_fallbacks"]
    if not taken:
        return None
    host = (
        c["host_fallbacks"] + c["host_fast"] + c["stage_fallbacks"]
        + c["breaker_fallback_topics"]
    )
    return 100.0 * (1.0 - host / taken)
