"""CPU time of one group of the broker's threads (the event loops; the
match path's threads off the loop: issue, resolvers, guard pool;
everything else) between the slice's two snapshots, per topic the matcher
took in between them (``program_spans.cpu_ns_per_pub``). The three sum to
the process's CPU."""

import program_spans


def read(ctx):
    return program_spans.read_part(ctx, program_spans.cpu_ns_per_pub, 1e3)
