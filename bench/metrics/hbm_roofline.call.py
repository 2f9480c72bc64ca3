"""The least time the window's calls needed on the chip (the larger of
least bytes over HBM bandwidth and FLOPs over peak) as a share of the
device's busy time, in %."""
from bench import reading


def read(rec):
    return reading.roofline_pct(rec, "call")
