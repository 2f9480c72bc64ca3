"""Pallas kernel events in the traced window per call."""
from bench import reading


def read(rec):
    return reading.kernels_per_request(rec, "call")
