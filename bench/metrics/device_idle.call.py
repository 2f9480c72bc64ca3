"""Share of the traced window in which no operation ran on the device,
in the call cells, in %."""
from bench import reading


def read(rec):
    return reading.idle_pct(rec, "call")
