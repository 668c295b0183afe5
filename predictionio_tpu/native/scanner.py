"""ctypes bindings for the native event-log scanner.

Builds ``libeventscan.so`` from eventlog_scanner.cpp on first use via
:mod:`predictionio_tpu.native.build` (artifact keyed by a SHA-256 of the
source *content* — an mtime key could silently serve a stale ``.so``)
and exposes ``scan_segments(paths) -> EventBatch``.  Falls back
gracefully: callers check ``native_available()`` and use the pure-Python
path otherwise.
"""

from __future__ import annotations

import ctypes
import logging
import os
import threading
import time
from pathlib import Path
from typing import List, Optional, Sequence

import numpy as np

from predictionio_tpu.native import build as _native_build

log = logging.getLogger("pio.native")

_SRC = Path(__file__).parent / "eventlog_scanner.cpp"
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def _build_and_load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _load_failed:
            return None
        try:
            lib = ctypes.CDLL(str(_native_build.build(_SRC, "libeventscan")))
            lib.scan_new.restype = ctypes.c_void_p
            lib.scan_add_file.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
            lib.scan_run.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                     ctypes.c_int64]
            lib.scan_run.restype = ctypes.c_int64
            lib.scan_error.argtypes = [ctypes.c_void_p]
            lib.scan_error.restype = ctypes.c_char_p
            lib.scan_fill.argtypes = [ctypes.c_void_p] + [ctypes.c_void_p] * 6
            lib.scan_fill.restype = None
            lib.scan_stats.argtypes = [ctypes.c_void_p,
                                       ctypes.POINTER(ctypes.c_double)]
            lib.scan_stats.restype = None
            lib.scan_dict_size.argtypes = [ctypes.c_void_p, ctypes.c_int]
            lib.scan_dict_size.restype = ctypes.c_int64
            lib.scan_dict_blob.argtypes = [ctypes.c_void_p, ctypes.c_int]
            lib.scan_dict_blob.restype = ctypes.POINTER(ctypes.c_char)
            lib.scan_dict_offsets.argtypes = [ctypes.c_void_p, ctypes.c_int]
            lib.scan_dict_offsets.restype = ctypes.POINTER(ctypes.c_int64)
            lib.scan_prop_count.argtypes = [ctypes.c_void_p]
            lib.scan_prop_count.restype = ctypes.c_int64
            for name in ("scan_prop_len", "scan_prop_codes_len"):
                fn = getattr(lib, name)
                fn.argtypes = [ctypes.c_void_p, ctypes.c_int]
                fn.restype = ctypes.c_int64
            lib.scan_prop_bind.argtypes = (
                [ctypes.c_void_p, ctypes.c_int] + [ctypes.c_void_p] * 5)
            lib.scan_prop_bind.restype = None
            lib.layout_width.argtypes = [
                ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
                ctypes.c_int32, ctypes.c_int32, ctypes.c_int32]
            lib.layout_width.restype = ctypes.c_int64
            lib.layout_fill.argtypes = [
                ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
                ctypes.c_int64, ctypes.c_int32, ctypes.c_int32, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int32)]
            lib.layout_fill.restype = ctypes.c_int32
            lib.scan_free.argtypes = [ctypes.c_void_p]
            _lib = lib
            return lib
        except Exception as e:  # compiler missing, build error, load error
            log.warning("native scanner unavailable (%s); using Python path", e)
            _load_failed = True
            return None


def native_available() -> bool:
    return _build_and_load() is not None


# which-codes of scan_dict_*: the four id dictionaries, the property keys,
# then one dictionary a property column
_DICT_PROP_KEYS = 4
_STAT_NAMES = ("files", "bytes", "ranges", "threads", "rows", "parse_s",
               "merge_s", "slow_strings", "slow_times")


def _export_dict(lib, handle, which: int) -> List[str]:
    """One merged dictionary's strings: the blob is decoded once, and an
    ASCII blob (byte offsets are then character offsets) is only sliced."""
    n = lib.scan_dict_size(handle, which)
    if n <= 0:
        return []
    offsets = np.ctypeslib.as_array(
        lib.scan_dict_offsets(handle, which), shape=(n + 1,)).tolist()
    blob = ctypes.string_at(lib.scan_dict_blob(handle, which), offsets[-1])
    if blob.isascii():
        text = blob.decode("ascii")
        return [text[offsets[i]:offsets[i + 1]] for i in range(n)]
    return [_decode(blob[offsets[i]:offsets[i + 1]]) for i in range(n)]


def _decode(b: bytes) -> str:
    # surrogatepass: JSON may legally carry lone surrogates (Python's own
    # json emits them); anything else malformed falls back to replacement
    try:
        return b.decode("utf-8", "surrogatepass")
    except UnicodeDecodeError:
        return b.decode("utf-8", "replace")


def scan_segments(paths: Sequence[os.PathLike], n_threads: int = 0):
    """Parse JSONL event segments into an EventBatch (native path)."""
    return _scan(paths, n_threads, 0)


def _scan(paths: Sequence[os.PathLike], n_threads: int, range_bytes: int):
    """``scan_segments`` with the size of the byte ranges the workers pull
    (0: the scanner's own, a few MB); tests force it small."""
    from predictionio_tpu.obs.spans import span
    from predictionio_tpu.store.columnar import EventBatch, IdDict, PropColumn

    lib = _build_and_load()
    if lib is None:
        raise RuntimeError("native scanner unavailable")
    if n_threads <= 0:
        n_threads = min(os.cpu_count() or 4, 16)
    handle = lib.scan_new()
    try:
        with span("native_scan") as rec:
            t0 = time.perf_counter()
            for p in paths:
                lib.scan_add_file(handle, str(p).encode())
            rows = lib.scan_run(handle, n_threads, range_bytes)
            if rows < 0:
                raise RuntimeError(lib.scan_error(handle).decode())

            # the scanner writes its columns straight into these
            cols = [np.empty(rows, dt) for dt in
                    (np.int32, np.int32, np.int32, np.int32, np.int64,
                     np.float32)]
            props = []
            for k in range(lib.scan_prop_count(handle)):
                n = lib.scan_prop_len(handle, k)
                col = PropColumn(
                    rows=np.empty(n, np.int64), kind=np.empty(n, np.int8),
                    num=np.empty(n, np.float64),
                    str_offs=np.empty(n + 1, np.int64),
                    codes=np.empty(lib.scan_prop_codes_len(handle, k),
                                   np.int32),
                    dict=None)
                lib.scan_prop_bind(
                    handle, k, col.rows.ctypes.data, col.kind.ctypes.data,
                    col.num.ctypes.data, col.str_offs.ctypes.data,
                    col.codes.ctypes.data)
                props.append(col)
            lib.scan_fill(handle, *(c.ctypes.data for c in cols))

            dicts = [IdDict.from_state(_export_dict(lib, handle, which))
                     for which in range(_DICT_PROP_KEYS)]
            keys = _export_dict(lib, handle, _DICT_PROP_KEYS)
            for k, col in enumerate(props):
                col.dict = IdDict.from_state(
                    _export_dict(lib, handle, _DICT_PROP_KEYS + 1 + k))
            batch = EventBatch(
                *cols, *dicts,
                prop_columns=dict(zip(keys, props)))
            stats = (ctypes.c_double * len(_STAT_NAMES))()
            lib.scan_stats(handle, stats)
            attrs = {name: (v if name.endswith("_s") else int(v))
                     for name, v in zip(_STAT_NAMES, stats)}
            # the rest of the span: allocating the arrays, decoding the
            # dictionaries
            attrs["export_s"] = max(time.perf_counter() - t0
                                    - attrs["parse_s"] - attrs["merge_s"], 0.0)
            rec["attrs"] = attrs
        return batch
    finally:
        lib.scan_free(handle)


def layout_chunks(user, item, chunk: int, n_chunks: int, pad_multiple: int = 8):
    """Chunk-grouped COO layout via the native O(n) counting pass:
    (lu [n_chunks, width], it [n_chunks, width], cnt [n_chunks]).

    Returns None ONLY when the native library is unavailable (callers fall
    back to numpy); invalid input — length mismatch, user ids outside
    [0, chunk*n_chunks) — raises ValueError loudly on this path just as
    callers validate for the numpy path."""
    lib = _build_and_load()
    if lib is None:
        return None
    user = np.ascontiguousarray(user, np.int32)
    item = np.ascontiguousarray(item, np.int32)
    if len(user) != len(item):
        raise ValueError(
            f"user/item length mismatch: {len(user)} vs {len(item)}")
    n = len(user)
    p32 = ctypes.POINTER(ctypes.c_int32)
    u_ptr = user.ctypes.data_as(p32)
    width = lib.layout_width(u_ptr, n, chunk, n_chunks, pad_multiple)
    if width < 0:
        raise ValueError(
            f"user ids outside [0, {chunk * n_chunks}) in layout_chunks")
    lu = np.zeros((n_chunks, int(width)), np.int32)
    it = np.zeros((n_chunks, int(width)), np.int32)
    cnt = np.zeros(n_chunks, np.int32)
    rc = lib.layout_fill(
        u_ptr, item.ctypes.data_as(p32), n, chunk, n_chunks, width,
        lu.ctypes.data_as(p32), it.ctypes.data_as(p32), cnt.ctypes.data_as(p32))
    if rc != 0:
        raise ValueError(f"native layout_fill failed (rc={rc})")
    return lu, it, cnt
