"""The MTTKRP's share of its roofline: the least time of one MTTKRP of
every mode (compulsory bytes over HBM bandwidth or operations over peak
rate, whichever is larger; ``chipbench/roofline.py``) over its measured
device time (``mttkrp_ms``)."""
from chipbench import roofline, spec


def read(ctx):
    measured_ms = spec.load_reader(spec.ROOT, "mttkrp_ms")(ctx)
    if measured_ms is None:
        return None
    peaks = roofline.peaks_for(ctx["device_kind"])
    least = roofline.least_seconds(
        roofline.sweep_flops(ctx["dims"], ctx["nnz"], ctx["rank"]),
        roofline.sweep_bytes(ctx["dims"], ctx["nnz"], ctx["rank"]), peaks)
    return 100.0 * least / (measured_ms / 1e3)
