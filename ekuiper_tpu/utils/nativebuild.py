"""Build-on-demand of the native libraries under native/.

`native/build/` is not committed: a checkout builds its libraries at first
use. A library found there is used only while it was built from the sources
the tree holds NOW — each install writes the sha256 of its sources (and the
Makefile) beside the library, and a mismatch means rebuild. A copied or
long-lived tree therefore never runs a decoder older than its source.
"""
from __future__ import annotations

import hashlib
import os
import subprocess
import sys
from typing import Dict, Sequence

from .infra import logger

NATIVE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "native"))
BUILD_DIR = os.path.join(NATIVE_DIR, "build")

_digests: Dict[tuple, str] = {}  # sources are fixed for a process's life


def lib_path(lib: str) -> str:
    return os.path.join(BUILD_DIR, lib)


def _source_digest(sources: Sequence[str]) -> str:
    key = tuple(sources)
    if key not in _digests:
        h = hashlib.sha256()
        for name in (*sources, "Makefile"):
            with open(os.path.join(NATIVE_DIR, name), "rb") as fh:
                h.update(fh.read())
        _digests[key] = h.hexdigest()
    return _digests[key]


def is_current(lib: str, sources: Sequence[str]) -> bool:
    """True when native/build/<lib> exists and was built from the sources
    as they are now."""
    try:
        with open(lib_path(lib) + ".src") as fh:
            stamp = fh.read().strip()
    except OSError:
        return False
    return os.path.exists(lib_path(lib)) and stamp == _source_digest(sources)


def build(lib: str, sources: Sequence[str], timeout: float = 180) -> bool:
    """Compile `lib` in a scratch directory and install it (library, then
    its source stamp) by atomic renames, so a loader never opens a
    half-written file."""
    scratch = f"build.tmp.{lib}.{os.getpid()}"
    scratch_dir = os.path.join(NATIVE_DIR, scratch)
    try:
        subprocess.run(
            ["make", "-C", NATIVE_DIR, f"BUILD={scratch}",
             f"PYTHON={sys.executable}", f"{scratch}/{lib}"],
            capture_output=True, timeout=timeout, check=True)
        os.makedirs(BUILD_DIR, exist_ok=True)
        os.replace(os.path.join(scratch_dir, lib), lib_path(lib))
        stamp_tmp = os.path.join(scratch_dir, "src")
        with open(stamp_tmp, "w") as fh:
            fh.write(_source_digest(sources))
        os.replace(stamp_tmp, lib_path(lib) + ".src")
        return True
    except (OSError, subprocess.SubprocessError) as exc:
        logger.warning("native build of %s failed: %s", lib, exc)
        return False
    finally:
        try:
            os.rmdir(scratch_dir)
        except OSError:
            pass
