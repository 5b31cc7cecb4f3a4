"""What the compiled programs had reserved for their temporaries on the
fullest chip at the most, ``memory_stats()["peak_bytes_reserved"]`` read
after the window beside ``peak_bytes_in_use``, in GB (1e9 bytes): the step's
above all. It is the reservation alone: the arrays are in
``device.peak_hbm_gb``, and the two peaks need not fall in one moment.
Nothing where the backend has no such key."""


def read(view):
    reserved = view.get("memory_reserved_peak_bytes")
    return reserved / 1e9 if reserved else None
