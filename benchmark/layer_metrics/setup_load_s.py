"""Set-up's load half, from the program's own clock: the open-to-close
wall of the trie's bulk loads (``TopicsIndex.bulk_load``, summed over the
outermost ones), as it stands at the traced slice's second snapshot. A
program whose snapshots lack it gives nothing."""

import loop_ledger


def read(ctx):
    sl = loop_ledger.load()
    return None if sl is None else sl.b.get("bulk_load_seconds")
