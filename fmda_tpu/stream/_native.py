"""Shared build/load bootstrap for the native C++ components.

Both ctypes bindings (ring bus, join scheduler) build the same `native/`
tree with make and load a shared library from `native/build/`; keeping the
bootstrap in one place means timeout/error-shaping fixes can't drift
between them.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import time
from typing import Dict, Type

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)

_loaded: Dict[str, ctypes.CDLL] = {}
#: lib name -> whether a ``make`` of this process (re)built it, for
#: reports that must say what they loaded (chip_smoke.py "host path")
built_this_run: Dict[str, bool] = {}
_IMPORTED_AT = time.time()


def build_and_load(lib_name: str, exc_cls: Type[Exception]) -> ctypes.CDLL:
    """Build and load ``native/build/<lib_name>``; cached per process.

    ``make`` runs on every first load — it is incremental, so an
    up-to-date library costs one no-op call — instead of trusting
    whatever ``.so`` happens to be on disk: ``native/build/`` is
    git-ignored, and a stale or foreign build there must not be what a
    fresh checkout of the same commit would never load.  Raises
    ``exc_cls`` with the compiler's stderr tail when the toolchain is
    missing or the build fails.
    """
    if lib_name in _loaded:
        return _loaded[lib_name]
    lib_path = os.path.join(_NATIVE_DIR, "build", lib_name)
    try:
        subprocess.run(
            ["make", "-C", _NATIVE_DIR],
            check=True, capture_output=True, timeout=120,
        )
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            OSError) as e:
        detail = ""
        if isinstance(e, subprocess.CalledProcessError):
            detail = f": {e.stderr.decode(errors='replace')[-500:]}"
        raise exc_cls(f"cannot build {lib_name} ({e}){detail}") from e
    if not os.path.exists(lib_path):
        raise exc_cls(f"build succeeded but {lib_name} missing")
    # one make builds every target, so compare against process start,
    # not against this call
    built_this_run[lib_name] = os.path.getmtime(lib_path) >= _IMPORTED_AT
    try:
        lib = ctypes.CDLL(lib_path)
    except OSError as e:
        raise exc_cls(f"cannot load {lib_path}: {e}") from e
    _loaded[lib_name] = lib
    return lib
