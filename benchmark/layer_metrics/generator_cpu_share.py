"""The busiest generator process's CPU seconds over the window: near 100
the generator, not the broker, is what the cell measures."""


def read(ctx):
    busiest = max((g["cpu_s"] for g in ctx["generators"]), default=None)
    return None if busiest is None else 100.0 * busiest / ctx["seconds"]
