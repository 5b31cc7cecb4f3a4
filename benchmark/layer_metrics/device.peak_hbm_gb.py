"""Peak device memory of the fullest chip after the window,
``memory_stats()["peak_bytes_in_use"]``, in GB (1e9 bytes)."""


def read(view):
    return view["memory_peak_bytes"] / 1e9 or None
