"""Broker process CPU time (every thread, ``time.process_time``) over
the window per 1,000 publishes the match plane took in."""


def read(ctx):
    topics = ctx["counters"]["topics"]
    return 1e3 * ctx["counters"]["cpu_s"] / (topics / 1e3) if topics else None
