"""Shared build-on-demand loader for the native C++ libraries (native/).

One protocol for every .so: look for it, `make` its SPECIFIC target when
absent (so one library's missing system dependency — e.g. libzstd for the
codec — cannot disable another's build), dlopen, apply the caller's symbol
configuration. Callers cache the result module-side; None means "use the
Python fallback", and the first load of each library says on stderr which
of the two it got."""

import ctypes
import os
import subprocess
import sys
import threading
from typing import Callable, Optional

NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")

_lock = threading.Lock()


def _make(make_dir: str, so_name: str, force: bool = False) -> None:
    subprocess.run(["make"] + (["-B"] if force else [])
                   + ["-C", make_dir, so_name],
                   capture_output=True, text=True, timeout=120, check=True)


def _load(so_name: str, configure, make_dir: str) -> ctypes.CDLL:
    so = os.path.join(make_dir, so_name)
    if not os.path.exists(so):
        _make(make_dir, so_name)
    try:
        lib = ctypes.CDLL(so)
        configure(lib)
    except AttributeError:
        # Stale build: the .so predates a symbol the caller now configures
        # (e.g. a loader built before psl_rrc_batch). Force-rebuild once and
        # retry; unlink first so a failed make cannot leave the stale
        # binary to be found again next run.
        os.unlink(so)
        _make(make_dir, so_name, force=True)
        lib = ctypes.CDLL(so)
        configure(lib)
    return lib


def load_native_lib(so_name: str,
                    configure: Callable[[ctypes.CDLL], None],
                    make_dir: str = "") -> Optional[ctypes.CDLL]:
    make_dir = make_dir or NATIVE_DIR
    with _lock:
        try:
            lib = _load(so_name, configure, make_dir)
        except (OSError, AttributeError, subprocess.SubprocessError) as e:
            why = (getattr(e, "stderr", None) or str(e)).strip().splitlines()
            print(f"NATIVE {so_name}: python fallback "
                  f"({type(e).__name__}: {why[-1] if why else ''})",
                  file=sys.stderr)
            return None
        print(f"NATIVE {so_name}: native ({os.path.join(make_dir, so_name)})",
              file=sys.stderr)
        return lib
