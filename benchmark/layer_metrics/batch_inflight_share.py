"""Share of the traced slice with at least one batch between dispatch
returned and sync done, as the host sees it: the ceiling on the device's
busy share (``program_spans.inflight_share``)."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    return None if sl is None else program_spans.inflight_share(sl)
