"""Device microseconds per traced call spent padding operands: the
self time of the `pad` ops among the traced window's `device_ops` (its
ten ops with the most self time; `bench.scopes.pad_seconds`) ÷ the
traced calls."""
from bench import scopes


def read(rec):
    if (rec["unit"] != "call" or rec["trace"] is None
            or not rec["traced_requests"]):
        return None
    return (1e6 * scopes.pad_seconds(rec["trace"].device_ops)
            / rec["traced_requests"])
