"""Trie nodes alive per subscription the trie holds, at the traced
slice's second snapshot (``TopicsIndex.particles`` over ``.held``): what
the deployment's filter shape costs the host trie: about 1 where
filters share their paths, 5.6 where levels 4-8 of nearly every path are
its own. A program whose snapshots lack the counts gives nothing."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or not sl.b.get("held") or "particles" not in sl.b:
        return None
    return sl.b["particles"] / sl.b["held"]
