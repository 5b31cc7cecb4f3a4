"""Rows the routed layers' row buffers held over the assignments held, over
the window (``zoo_moe_rows_run_total`` over ``zoo_moe_assignments_total
{held=true}``, from ``model.last_fit_report``): 1 is a buffer with no empty
row, router width over experts held (8 in the decoder cell) the static
worst case under a balanced router. A program whose report has no such
counter (before PR 29) reads nothing."""


def read(view):
    report = (getattr(view["model"], "last_fit_report", None) or {}).get("moe")
    if not report:
        return None
    return report.get("rows_run_over_held")
