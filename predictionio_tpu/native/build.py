"""Shared lazy in-tree build for the native cores.

One helper both bindings modules (``scanner``, ``core``) go through: the
``.so`` artifact under ``native/_build`` is keyed by a SHA-256 of the
C++ source *content* — an mtime key can silently serve a stale library
after a checkout, a copy, or an edit that lands in the same clock
second, and a stale data-plane core is a parity bug, not a perf bug.

``scripts/build_native.sh`` calls :func:`build` eagerly; everything else
builds lazily on first use and degrades to the pure-Python path when no
toolchain exists (``compiler()`` is None).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import shutil
import subprocess
import tempfile
from pathlib import Path
from typing import Optional

log = logging.getLogger("pio.native")

BUILD_DIR = Path(__file__).parent / "_build"

_CXX_CANDIDATES = ("g++", "c++", "clang++")


def compiler() -> Optional[str]:
    """First available C++ compiler on PATH, or None (no toolchain)."""
    for cxx in _CXX_CANDIDATES:
        if shutil.which(cxx):
            return cxx
    return None


def source_key(src: Path) -> str:
    """Content hash of ``src`` — the build-cache key (first 16 hex
    chars: enough to never collide between edits of one file)."""
    return hashlib.sha256(src.read_bytes()).hexdigest()[:16]


def artifact_path(src: Path, stem: str) -> Path:
    return BUILD_DIR / f"{stem}-{source_key(src)}.so"


def build(src: Path, stem: str, timeout: int = 300) -> Path:
    """Compile ``src`` into its content-keyed artifact (no-op when the
    artifact already exists).  Raises on any build failure — callers
    that want graceful degradation wrap this (``load``).

    Safe under concurrent first use (six test workers on a fresh
    checkout): each builder compiles into a temporary file of its own
    and renames it onto the artifact, so whoever finishes first wins and
    the others replace it with identical bytes or, where their own
    compile fails, load the winner's file.  Only artifacts of OTHER keys
    (older sources) are removed, and only after this key's is in place."""
    so = artifact_path(src, stem)
    if so.exists():
        return so
    cxx = compiler()
    if cxx is None:
        raise RuntimeError("no C++ compiler on PATH")
    BUILD_DIR.mkdir(exist_ok=True)
    fd, tmp_name = tempfile.mkstemp(prefix=f"{so.name}.", suffix=".tmp",
                                    dir=BUILD_DIR)
    os.close(fd)
    tmp = Path(tmp_name)
    cmd = [cxx, "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           str(src), "-o", str(tmp)]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=timeout)
        # rename-into-place: nobody ever loads a half-written .so
        tmp.replace(so)
    except Exception:
        tmp.unlink(missing_ok=True)
        if not so.exists():     # a sibling may have finished meanwhile
            raise
    for old in BUILD_DIR.glob(f"{stem}-*.so"):
        if old != so:
            old.unlink(missing_ok=True)
    return so


def load(src: Path, stem: str) -> Optional[ctypes.CDLL]:
    """Build-if-needed and dlopen; None when the toolchain is missing or
    the build/load fails (logged once by the caller)."""
    try:
        return ctypes.CDLL(str(build(src, stem)))
    except Exception as e:  # compiler missing, build error, load error
        log.warning("native %s unavailable (%s); using Python path", stem, e)
        return None
