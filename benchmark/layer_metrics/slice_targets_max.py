"""The most target ids one completion slice of a staged batch has looked
up at once (``_Ops.slice_targets_max``, a high-water mark since the
broker started, warm-up included), at the traced slice's second
snapshot: what one turn of the event loop may have to deliver before any
timer or read runs. A program whose snapshots lack it gives nothing."""

import program_spans


def read(ctx):
    sl = program_spans.load()
    if sl is None or "slice_targets_max" not in sl.b:
        return None
    return sl.b["slice_targets_max"]
