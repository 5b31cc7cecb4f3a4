"""Token-to-expert assignments the routed layers placed with no expert over
the window (``zoo_moe_dropped_assignments_total``, from
``model.last_fit_report``): the layer has no capacity, so this reads 0."""


def read(view):
    report = (getattr(view["model"], "last_fit_report", None) or {}).get("moe")
    if not report:
        return None
    return report["dropped"]
