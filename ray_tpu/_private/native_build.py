"""Shared compile-if-stale + dlopen helper for the native tier.

One place owns the g++ invocation and staleness check; the per-library
modules (native_store.py, cpp_client.py) only declare their prototypes.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import List, Optional

from ray_tpu._private.platform import REPO_ROOT

NATIVE_DIR = os.path.join(REPO_ROOT, "native")


def build_native_so(src_name: str, out_name: str,
                    libs: Optional[List[str]] = None) -> Optional[str]:
    """Compile ``native/<src_name>`` into ``native/<out_name>`` when the
    source is newer; returns the .so path, or None where there is no
    g++ (callers then take their pure-Python path). A compile that
    FAILS raises: that is a broken tree, not a missing tool."""
    src = os.path.join(NATIVE_DIR, src_name)
    out = os.path.join(NATIVE_DIR, out_name)
    if not os.path.exists(src):
        return None
    if os.path.exists(out) and (
            os.path.getmtime(out) >= os.path.getmtime(src)):
        return out
    # build beside the target and rename: on a fresh checkout the driver,
    # its daemons and their workers all reach here at once, and nobody
    # may dlopen a half-written file
    tmp = f"{out}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-Wall",
             "-o", tmp, src, *(libs or [])],
            check=True, capture_output=True, timeout=120)
    except FileNotFoundError:
        return None
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"native build of {src_name} failed:\n"
            f"{e.stderr.decode(errors='replace')[-2000:]}") from e
    os.replace(tmp, out)
    return out


def load_native_so(src_name: str, out_name: str,
                   libs: Optional[List[str]] = None
                   ) -> Optional[ctypes.CDLL]:
    path = build_native_so(src_name, out_name, libs)
    return ctypes.CDLL(path) if path else None
