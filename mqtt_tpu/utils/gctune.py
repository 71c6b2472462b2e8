"""CPython GC tuning for the broker's allocation profile.

The broker's hot paths (packet decode, publish fan-out, device-match
result materialization) allocate hundreds of thousands of short-to-medium
lived objects per second. CPython's default gen-0 threshold (700
allocations) makes the collector run hundreds of times per match batch,
re-scanning the same young survivors each time, at a cost comparable to
the resolve work itself on a large batch. The reference
broker runs on Go's concurrent collector and never pays an equivalent
stop-the-world tax, so tuning this is table stakes for host-plane parity.

``tune_for_throughput`` raises the thresholds so full young-gen scans
happen per ~100K allocations instead of per 700. ``freeze_index`` moves
the current object graph (e.g. a freshly built million-entry flat index)
into the permanent generation, removing it from every future GC scan;
refcounting still reclaims replaced snapshots immediately.
"""

from __future__ import annotations

import gc

_TUNED = False


def tune_for_throughput() -> None:
    """Raise GC generation thresholds for allocation-heavy serving.

    Idempotent, and respectful of an embedder that already disabled the
    collector entirely.
    """
    global _TUNED
    if _TUNED or not gc.isenabled():
        return
    gen0, gen1, gen2 = gc.get_threshold()
    gc.set_threshold(max(gen0, 100_000), max(gen1, 50), max(gen2, 50))
    _TUNED = True


def freeze_index() -> None:
    """Move all currently tracked objects to the permanent generation.

    Call after building a large long-lived structure (flat match index,
    restored retained-message store) so subsequent collections never
    re-scan it. Objects later dropped from the frozen set are still freed
    by reference counting.

    This is deliberately NOT called by the live server: ``gc.freeze`` is
    all-or-nothing, and freezing mid-serving would also freeze whatever
    transient asyncio state (tasks, futures, exception tracebacks — which
    commonly form reference cycles) happens to be alive, leaking any such
    cycles permanently. Use it from batch/benchmark processes where the
    object graph at call time is known to be the long-lived index.
    """
    gc.freeze()
