"""Share of the window that ``fit`` spends beside its steps, on the host's
clock: ``host_s`` of ``model.last_fit_report`` for ``fit.enter`` (resume,
cloning the trees in, building steps), ``epoch.tail`` (reading the epoch's
mean loss back, after the device has drained) and ``epoch.publish`` (cloning
the trees out, callbacks, summaries), over ``wall_s``."""


def read(view):
    report = getattr(view["model"], "last_fit_report", None)
    if not report:
        return None
    host = report["host_s"]
    return 100.0 * (host["fit.enter"] + host["epoch.tail"]
                    + host["epoch.publish"]) / report["wall_s"]
