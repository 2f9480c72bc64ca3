"""Microseconds from entering `Executable.run` to its return, before
the wait for the device, mean over the calls of the measured window,
which runs with the profiler off."""


def read(rec):
    if rec["unit"] != "call":
        return None
    return 1e6 * rec["dispatch_s"] / rec["requests"]
