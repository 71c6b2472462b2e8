"""The longest collection of generation 0 or 1 that ended inside the
traced slice (``young_recent``: the newest 64 of over a millisecond, from
the collector's hook, which is always on); 0 where none did. Beside
``loop_stall_max_ms`` it convicts or clears a young collection of the
loop's longest stall. A program whose snapshots lack the list gives
nothing."""

import loop_ledger


def read(ctx):
    sl = loop_ledger.load()
    pauses = None if sl is None else loop_ledger.young_pauses(sl)
    if pauses is None:
        return None
    return max((ns for _end, ns, _gen in pauses), default=0) / 1e6
