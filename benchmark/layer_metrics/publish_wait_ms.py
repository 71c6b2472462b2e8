"""Where a publish waits, from ``submit()`` to its flush: the mean per
publish over the traced slice's batches of one of five stretches
(``program_spans.publish_wait_ns``). ``publish_wait_ms.<part>`` and
``publish_wait_ms.<part>.steady``; the five sum to its stay in the broker."""

import program_spans


def read(ctx):
    return program_spans.read_part(ctx, program_spans.publish_wait_ns, 1e6)
