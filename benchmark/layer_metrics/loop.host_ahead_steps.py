"""How many optimizer steps the host is ahead of the device: the median, over
the window's dispatches, of the steps dispatched and not yet finished
(``inflight.median`` of ``model.last_fit_report``; ``is_ready()`` on the loss
arrays the loop holds). 0 means the loop, not the chip, sets the pace."""


def read(view):
    report = getattr(view["model"], "last_fit_report", None)
    if not report:
        return None
    return report["inflight"]["median"]
