"""Program calls completed per second of the window: every call sent,
with up to the traffic's `ahead` in flight (`bench/loop.py`), over the
time from the first send to the completion of the last."""


def read(rec):
    if rec["unit"] != "call":
        return None
    return rec["requests"] / rec["window_s"]
