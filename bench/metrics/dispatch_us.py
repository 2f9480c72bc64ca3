"""Microseconds the client's `send` takes (`bench.kinds.Calls.send`:
the merge of the resident and the pooled inputs and the call of
`Executable.run`, up to its return, before the wait for the device),
mean over the calls of the measured window, which runs with the
profiler off."""


def read(rec):
    if rec["unit"] != "call":
        return None
    return 1e6 * rec["dispatch_s"] / rec["requests"]
