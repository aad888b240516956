"""Shared loader for the first-party C++ helper libraries (native/).

Single source of truth for the ``native/build/<lib>.so`` path resolution used
by both the tokenizer bindings (tokenizer/native.py) and the host-coordination
bindings (parallel/dist.py). ``native/build/`` is git-ignored, so a fresh
checkout has no library: the first load builds it with ``make -C native``
(g++, no dependencies, about a second). A build that fails is logged as an
ERROR once per process and the caller gets ``None``; callers that cannot do
without the library (chip_smoke.py) treat that as fatal.
"""

from __future__ import annotations

import ctypes
import fcntl
import logging
import os
import subprocess
import threading
from typing import Dict, Optional

logger = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
_BUILD_DIR = os.path.join(_NATIVE_DIR, "build")

_cache: Dict[str, ctypes.CDLL] = {}
_build_lock = threading.Lock()
_build_failed = False


def native_lib_path(name: str) -> str:
    return os.path.join(_BUILD_DIR, name)


def build_native() -> bool:
    """``make -C native`` under a cross-process file lock (fleet engines and
    multi-process worlds start together on a fresh checkout). True when the
    build succeeded; a failure is remembered so each process tries once."""
    global _build_failed
    with _build_lock:
        if _build_failed:
            return False
        try:
            os.makedirs(_BUILD_DIR, exist_ok=True)
            with open(os.path.join(_BUILD_DIR, ".lock"), "w") as lock:
                fcntl.flock(lock, fcntl.LOCK_EX)
                proc = subprocess.run(
                    ["make", "-C", _NATIVE_DIR], capture_output=True,
                    text=True, timeout=300,
                )
        except (OSError, subprocess.SubprocessError) as e:
            _build_failed = True
            logger.error("native build could not run (make -C %s): %s",
                         _NATIVE_DIR, e)
            return False
        if proc.returncode != 0:
            _build_failed = True
            logger.error(
                "native build failed (make -C %s, rc %d):\n%s",
                _NATIVE_DIR, proc.returncode, proc.stderr[-2000:],
            )
            return False
        return True


def load_native_lib(name: str) -> Optional[ctypes.CDLL]:
    """CDLL for ``native/build/<name>``, building it on first use; None
    when it cannot be built here."""
    if name in _cache:
        return _cache[name]
    path = native_lib_path(name)
    if not os.path.exists(path) and not (
        build_native() and os.path.exists(path)
    ):
        return None
    lib = ctypes.CDLL(path)
    _cache[name] = lib
    return lib
