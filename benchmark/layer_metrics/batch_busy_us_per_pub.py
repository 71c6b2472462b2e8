"""Host time inside one of a device batch's busy spans (tokenize, H2D +
dispatch, the blocking D2H sync, resolve), summed over the traced slice's
batches, per topic in them (``program_spans.batch_busy_ns_per_pub``)."""

import program_spans


def read(ctx):
    return program_spans.read_part(ctx, program_spans.batch_busy_ns_per_pub, 1e3)
