"""The traced slice's longest event-loop iteration (from one ``select()``'s
return to the next call) and what was inside it, in milliseconds
(``loop_ledger.stall_split_ns``): ``.busy`` its length, ``.ingest`` the
read loops' frame loops in it (publishes and acks), ``.fanout`` fan-out
(with the slice's joined writes), ``.gc`` collections (inside either, or
in what is left). ``busy`` less ``ingest`` and ``fanout`` is the
iteration's unnamed rest. A program without the loop's ledger gives
nothing."""

import loop_ledger


def read(ctx):
    sl = loop_ledger.load()
    if sl is None:
        return None
    ns = loop_ledger.stall_split_ns(sl).get(ctx["metric"].split(".")[1])
    return None if ns is None else ns / 1e6
