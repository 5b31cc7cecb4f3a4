"""Share of the window that the loop waited for its input pipeline: the
``GoodputLedger("train")`` category ``data_wait`` over the window, from
``zoo_badput_seconds_total{category="data_wait"}``."""


def read(view):
    return 100.0 * view["data_wait_s"] / view["window_s"]
