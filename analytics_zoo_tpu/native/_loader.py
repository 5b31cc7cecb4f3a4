"""Shared loader for the host-side C++ libraries under ``native/``
(``zoo_io.cc``, ``zoo_image.cc``). One place owns the build rule so the
compiler flags can't drift between libraries (they mirror
``native/Makefile``), and first-use builds are concurrency-safe: the
compile targets a pid-unique temp path and ``os.replace``s into place, so
two processes racing the same missing ``.so`` can never leave a corrupt
half-written library behind (a corrupt file would otherwise look newer
than its source and suppress every future rebuild)."""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
from typing import Optional

log = logging.getLogger("analytics_zoo_tpu.native")

NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")

# keep in sync with native/Makefile
CXXFLAGS = ["-O2", "-std=c++17", "-fPIC", "-Wall"]
LDFLAGS = ["-shared", "-pthread"]


def build_and_load(so_name: str, src_name: str) -> Optional[ctypes.CDLL]:
    """dlopen ``native/<so_name>``, building it from ``native/<src_name>``
    first when missing or older than the source (the ``.so`` files are
    git-ignored: a fresh checkout builds them here, at first use).
    Returns None on any failure — a missing ``g++``, a failed build — and
    says so at WARNING, because the callers then run their pure-Python
    paths for the life of the process."""
    so = os.path.join(NATIVE_DIR, so_name)
    src = os.path.join(NATIVE_DIR, src_name)
    try:
        if (not os.path.exists(so)
                or os.path.getmtime(so) < os.path.getmtime(src)):
            tmp = f"{so}.{os.getpid()}.tmp"
            try:
                subprocess.run(
                    ["g++", *CXXFLAGS, src, *LDFLAGS, "-o", tmp],
                    check=True, capture_output=True, timeout=120)
                os.replace(tmp, so)   # atomic: winners fully overwrite
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
            log.info("built native library %s", so)
        return ctypes.CDLL(so)
    except Exception as e:  # noqa: BLE001 — any failure → Python fallback
        log.warning("native library %s unavailable (%s); the pure-Python "
                    "fallback is in use", so_name, e)
        return None
