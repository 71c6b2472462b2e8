"""How full the event loop's core is, from the loop itself: 1 − the time
its ``select()`` calls that could block took (``pollw_ns``: idle time,
plus the call) over the traced slice, in percent. From the timing frame
the program stands around its loop's selector while the slice's profiler
session is live (``loop_ledger``); a program without one gives nothing."""

import loop_ledger


def read(ctx):
    sl = loop_ledger.load(*loop_ledger.FRAME)
    if sl is None or loop_ledger.delta(sl, "t_ns") <= 0:
        return None
    return 100.0 * (1.0 - loop_ledger.delta(sl, "pollw_ns") / loop_ledger.delta(sl, "t_ns"))
