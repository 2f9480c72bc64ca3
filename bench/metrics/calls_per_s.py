"""Program calls completed per second of the window, each sent after
the previous one finished."""


def read(rec):
    if rec["unit"] != "call":
        return None
    return rec["requests"] / rec["window_s"]
