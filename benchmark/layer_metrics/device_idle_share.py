"""1 minus the union of device-operation intervals over the traced
slice (``trace_reduce.py``)."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace.get("device_planes") or trace["slice_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["slice_s"])
