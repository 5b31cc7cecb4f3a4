"""Seconds the set-up spent in compilation or in loading compiled programs:
``zoo_jit_compile_seconds`` summed over ``fn=``, read before the window."""


def read(view):
    return view["setup_compile_s"]
