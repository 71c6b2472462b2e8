"""The event loop's time a publish by what it went on, from the loop's
own ledger over the traced slice (``loop_ledger.per_pub_ns``), in
microseconds: ``.poll`` (the ``select()`` calls), ``.send`` (the calls
that reached a socket) and ``.gc`` (collections of every generation),
both of which lie inside ingest, fan-out or the rest, and ``.rest`` (the
iterations less ingest, acks and fan-out: asyncio's transport reads, task
steps, timers). Divides by the topics the matcher took in between the
snapshots: where a slice holds a few batches of 40 it reads in steps."""

import loop_ledger


def read(ctx):
    sl = loop_ledger.load(*loop_ledger.FRAME)
    if sl is None:
        return None
    ns = loop_ledger.per_pub_ns(sl).get(ctx["metric"].split(".")[1])
    return None if ns is None else ns / 1e3
