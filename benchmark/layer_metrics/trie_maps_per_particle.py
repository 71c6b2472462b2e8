"""Containers alive per trie node at the traced slice's second snapshot
(``TopicsIndex.particle_maps`` over ``.particles``): children dicts and
subscription, shared and inline maps. 4 where every node is born with
all of them, near 1 where a node makes a map with its first entry of the
kind. A program whose snapshots lack the counts gives nothing."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or not sl.b.get("particles") or "particle_maps" not in sl.b:
        return None
    return sl.b["particle_maps"] / sl.b["particles"]
