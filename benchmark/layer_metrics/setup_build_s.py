"""Set-up's build half, from the program's own clock: the one build of
the match table that ended the newest bulk load (the trie's walk, the
flat index, the upload: ``DeltaMatcher.bulk_build_seconds``), as it stands
at the traced slice's second snapshot. A program whose snapshots lack it
gives nothing."""

import loop_ledger


def read(ctx):
    sl = loop_ledger.load()
    return None if sl is None else sl.b.get("bulk_build_seconds")
