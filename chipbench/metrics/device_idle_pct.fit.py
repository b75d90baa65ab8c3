"""Share of the fit window in which no op runs on the device (profiler
trace; idle = 1 - union of op intervals / window)."""


def read(ctx):
    if ctx["kind"] != "sweeps" or ctx["trace"] is None:
        return None
    return ctx["trace"].idle_pct
