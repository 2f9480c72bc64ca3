"""Seconds from the start of the process to the start of the window:
imports, the chip, the data, compiling (or loading from the cache)
and warming the cell's entry."""


def read(rec):
    return rec["setup_s"]
