"""Share of the window in which the device had training steps to run:
``ledger.device_step / wall_s`` of ``model.last_fit_report``, the report of
the last ``fit`` before the readers run, which is the window. The ledger
books a host interval as ``device_step`` when the loop's in-flight probe
finds dispatched steps unfinished at its end."""


def read(view):
    report = getattr(view["model"], "last_fit_report", None)
    if not report or not report["ledger"]:
        return None
    return 100.0 * report["ledger"]["device_step"] / report["wall_s"]
