"""Seconds of loading compiled programs from the persistent cache (hashing the
key, the read, deserialization), as JAX's own monitoring events report them:
``zoo_xla_compile_seconds_total`` summed over the instrumented ``fn`` labels
only (a reader's own ``.trace().lower()`` books under ``uninstrumented``). The
window compiles nothing, so the seconds are set-up's."""

PHASES = ("cache_load",)


def read(view):
    try:
        from analytics_zoo_tpu.observability.compile import (
            UNINSTRUMENTED, xla_compile_totals)
    except ImportError:         # a program without the compile listeners
        return None
    return sum(seconds.get(phase, 0.0)
               for fn, seconds in xla_compile_totals().items()
               if fn != UNINSTRUMENTED for phase in PHASES)
