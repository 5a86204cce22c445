"""Shared lazy builder/loader for the C++ runtime libraries under cpp/.

Both native modules (data readers, control-plane mailbox) follow the same
protocol: invoke ``make -C cpp`` on first use (a no-op when fresh, a
rebuild when sources are newer than a stale .so), serialized across
processes by an flock (the launcher starts several local workers at once;
without it two g++ runs can interleave writes to the .so while a third
dlopens the torso), then dlopen and let the caller declare prototypes.
``cpp/build/`` is git-ignored, so a binary found there is only trusted
when ``make`` has just vouched for it against the committed sources: if
the build cannot run (no compiler, no make) the loader says so on stderr
and returns ``None`` (callers take their Python/zmq paths) — it never
loads a leftover ``.so`` of unknown origin.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading
from typing import Callable, Optional

REPO_CPP = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "cpp")
_lock = threading.Lock()
_cache: dict[str, Optional[ctypes.CDLL]] = {}


def load_native_lib(
    lib_filename: str,
    declare: Callable[[ctypes.CDLL], None],
) -> Optional[ctypes.CDLL]:
    """Build (lazily, flock-serialized) and load ``cpp/build/<lib_filename>``.
    ``declare(lib)`` sets argtypes/restypes; it may raise AttributeError for
    optional symbols it handles itself. Returns None when the library
    cannot be built from the sources in this checkout (cached — one
    attempt per process)."""
    with _lock:
        if lib_filename in _cache:
            return _cache[lib_filename]
        lib_path = os.path.join(REPO_CPP, "build", lib_filename)
        try:
            os.makedirs(os.path.join(REPO_CPP, "build"), exist_ok=True)
            import fcntl

            with open(os.path.join(REPO_CPP, "build", ".lock"), "w") as lk:
                fcntl.flock(lk, fcntl.LOCK_EX)
                subprocess.run(["make", "-C", REPO_CPP], check=True,
                               capture_output=True, timeout=120)
        except (OSError, subprocess.SubprocessError) as e:
            print(f"minips_tpu: {lib_filename} not built from cpp/ "
                  f"({type(e).__name__}: {e}); using the Python path",
                  file=sys.stderr)
            _cache[lib_filename] = None
            return None
        try:
            lib = ctypes.CDLL(lib_path)
            declare(lib)
        except OSError:
            _cache[lib_filename] = None
            return None
        _cache[lib_filename] = lib
        return lib


def loaded_libs() -> list[str]:
    """Names of the native libraries this process has loaded."""
    with _lock:
        return sorted(k for k, v in _cache.items() if v is not None)
