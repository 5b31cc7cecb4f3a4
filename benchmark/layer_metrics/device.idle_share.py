"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window, averaged over the
chips (``lib/trace.py``)."""


def read(view):
    tr = view["trace"]
    if tr is None:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
