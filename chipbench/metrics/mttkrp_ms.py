"""Device milliseconds of one MTTKRP of every mode, summed over the modes:
the device-busy time inside the harness's ``probe`` spans, each of which
holds one call of the program's public ``mttkrp`` at the plan's impl and
the fit's own factors, averaged over the calls of a mode."""


def read(ctx):
    if ctx["kind"] != "sweeps" or ctx["trace"] is None:
        return None
    busy = ctx["trace"].span_busy("probe")
    calls = ctx["probe_calls"]
    if len(busy) != calls * ctx["modes"] or min(busy) <= 0.0:
        return None
    return 1e3 * sum(busy) / calls
