"""How uneven the routed experts' loads are: the largest held expert's
tokens over the held experts' mean, in the worst layer, in the last step of
the window (``zoo_moe_load_max_over_mean``, from ``model.last_fit_report``).
1 is even."""


def read(view):
    report = (getattr(view["model"], "last_fit_report", None) or {}).get("moe")
    if not report:
        return None
    return max(layer["load_max_over_mean"]
               for layer in report["layers"].values())
