"""The served match kernels' share of their roofline over the traced
slice: the least time the chip could take for the topics the slice
matched (``roofline.least_seconds``: HBM-bound gathers) over the device
time of the ``flat_match`` programs in the trace (``_compact_core`` and
``_packed_core`` as jitted). Nothing to read — no trace, no such program
in it, or no topic matched — returns nothing, never 0."""

import roofline

PROGRAMS = ("_compact_core", "_packed_core", "flat_match")


def read(ctx):
    trace, index = ctx["trace"], ctx["index"]
    if not trace or not index:
        return None
    kernel_s = sum(
        s for name, s in trace["kernels"].items()
        if any(p in name for p in PROGRAMS)
    )
    device_topics = (
        trace["counters"]["topics"] - trace["counters"]["host_fast"]
    )
    if kernel_s <= 0 or device_topics <= 0:
        return None
    least = roofline.least_seconds(
        device_topics, index["patterns"], index["levels"], ctx["device_kind"]
    )
    return 100.0 * least / kernel_s
