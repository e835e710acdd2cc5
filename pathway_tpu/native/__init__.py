"""Native (C++) runtime kernels, loaded via ctypes.

The reference engine keeps its host-side hot loops native (Rust: ``src/engine/value.rs``
key fingerprinting, ``src/connectors/data_format.rs`` parsers). This package builds the
TPU-native counterparts from ``csrc/pathway_native.cc`` with g++ on first import (cached
as a shared object next to this file) and exposes them behind the same contracts as the
pure-Python fallbacks in ``internals/keys.py`` / ``io/fs.py``. When no toolchain is
available everything degrades to the Python paths; ``unavailable_reason()`` says why,
so a caller that needs the native library (``chip_smoke.py``) can fail with the cause.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
from typing import Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "..", "..", "csrc", "pathway_native.cc")
_SO = os.path.join(_HERE, "_pathway_native.so")

_lib: Optional[ctypes.CDLL] = None
_tried = False
_reason: Optional[str] = None  # why get_lib() returned None


def unavailable_reason() -> Optional[str]:
    """Why :func:`get_lib` returned None (missing source, xxhash header or g++,
    a failed compile or load); None while the library is loaded or untried."""
    return _reason


def _xxhash_include_dir() -> Optional[str]:
    """xxhash ships header-only inside pyarrow's vendored tree in this image."""
    try:
        import pyarrow

        cand = os.path.join(
            os.path.dirname(pyarrow.__file__), "include", "arrow", "vendored", "xxhash"
        )
        if os.path.exists(os.path.join(cand, "xxhash.h")):
            return cand
    except Exception:
        pass
    for cand in ("/usr/include", "/usr/local/include"):
        if os.path.exists(os.path.join(cand, "xxhash.h")):
            return cand
    return None


def _build(force: bool = False) -> Optional[str]:
    global _reason
    src = os.path.abspath(_SRC)
    if not os.path.exists(src):
        _reason = f"source not found: {src}"
        return None
    if not force and os.path.exists(_SO) and os.path.getmtime(_SO) >= os.path.getmtime(src):
        return _SO
    include = _xxhash_include_dir()
    if include is None:
        _reason = "xxhash.h not found (pyarrow's vendored tree, /usr/include, /usr/local/include)"
        return None
    import sysconfig

    py_include = sysconfig.get_paths()["include"]
    tmp = f"{_SO}.{os.getpid()}.tmp"  # per-pid: concurrent spawned processes may race
    cmd = [
        "g++",
        "-O3",
        "-std=c++17",
        "-shared",
        "-fPIC",
        "-march=native",
        f"-I{include}",
        f"-I{py_include}",
        src,
        "-o",
        tmp,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        return _SO
    except (OSError, subprocess.SubprocessError) as exc:
        stderr = getattr(exc, "stderr", None) or b""
        _reason = f"g++ build failed: {exc!r} {stderr[-400:].decode(errors='replace')}".strip()
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None


def get_lib() -> Optional[ctypes.CDLL]:
    """The native library, building it on first use; None when unavailable."""
    global _lib, _tried, _reason
    if _tried:
        return _lib
    _tried = True
    if os.environ.get("PATHWAY_TPU_DISABLE_NATIVE"):
        _reason = "PATHWAY_TPU_DISABLE_NATIVE is set"
        return None
    path = _build()
    if path is None:
        return None
    try:
        # PyDLL: calls keep the GIL — required for the pyobject column kind, which
        # walks PyObject* arrays with CPython C-API calls
        lib = ctypes.PyDLL(path)
    except OSError as exc:
        _reason = f"dlopen failed: {exc}"
        return None
    if not hasattr(lib, "pwtpu_hash_upsert"):
        # stale prebuilt .so from older source (mtime comparisons can lie across
        # archive extraction / layer caching): force one rebuild — compiled to a
        # temp path and swapped in only on success, so a failed compile (e.g. no
        # toolchain on the deployment host) leaves the existing library intact.
        # The reload must use a FRESH path — glibc dedupes dlopen by pathname, so
        # reloading the replaced file at the same path returns the stale handle.
        path = _build(force=True)
        if path is None:
            return None
        import shutil

        fresh = f"{_SO}.reload.{os.getpid()}"
        try:
            shutil.copyfile(path, fresh)
            lib = ctypes.PyDLL(fresh)
        except OSError as exc:
            _reason = f"dlopen of the rebuilt library failed: {exc}"
            return None
        finally:
            try:
                os.unlink(fresh)  # the mapping survives the unlink on Linux
            except OSError:
                pass
        if not hasattr(lib, "pwtpu_hash_upsert"):
            _reason = "rebuilt library lacks pwtpu_hash_upsert"
            return None

    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.pwtpu_hash_typed.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int32,
        ctypes.c_uint64,
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.py_object,
        ctypes.py_object,
        u64p,
        u64p,
    ]
    lib.pwtpu_hash_typed.restype = ctypes.c_int64
    lib.pwtpu_hash_upsert.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int32,
        ctypes.c_uint64,
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.py_object,
        ctypes.py_object,
        ctypes.c_void_p,
        u64p,
        u64p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_uint8),
    ]
    lib.pwtpu_hash_upsert.restype = ctypes.c_int64
    lib.pwtpu_hash_serialized.argtypes = [
        ctypes.c_char_p,
        u64p,
        ctypes.c_uint64,
        u64p,
        u64p,
    ]
    lib.pwtpu_hash_serialized.restype = None
    lib.pwtpu_sequential_keys.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.c_int64,
        ctypes.c_uint64,
        u64p,
        u64p,
    ]
    lib.pwtpu_sequential_keys.restype = None
    lib.pwtpu_split_dsv.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.c_char,
        ctypes.c_char_p,
        u64p,
        u64p,
        ctypes.POINTER(ctypes.c_uint8),
        u64p,
        u64p,
    ]
    lib.pwtpu_split_dsv.restype = ctypes.c_uint64
    lib.pwtpu_parse_dsv_rows.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.c_char,
        ctypes.py_object,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int32,
        ctypes.py_object,
    ]
    lib.pwtpu_parse_dsv_rows.restype = ctypes.py_object
    i64p = ctypes.POINTER(ctypes.c_int64)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.pwtpu_combine_keys.argtypes = [
        u64p, u64p,
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint8),
        ctypes.c_int64, ctypes.c_uint64, u64p,
    ]
    lib.pwtpu_combine_keys.restype = None
    lib.pwtpu_idx_new.argtypes = [ctypes.c_uint64]
    lib.pwtpu_idx_new.restype = ctypes.c_void_p
    lib.pwtpu_idx_free.argtypes = [ctypes.c_void_p]
    lib.pwtpu_idx_free.restype = None
    lib.pwtpu_idx_len.argtypes = [ctypes.c_void_p]
    lib.pwtpu_idx_len.restype = ctypes.c_int64
    lib.pwtpu_idx_slot_bound.argtypes = [ctypes.c_void_p]
    lib.pwtpu_idx_slot_bound.restype = ctypes.c_int64
    lib.pwtpu_idx_upsert.argtypes = [ctypes.c_void_p, u64p, ctypes.c_int64, i64p, u8p]
    lib.pwtpu_idx_upsert.restype = None
    lib.pwtpu_idx_lookup.argtypes = [ctypes.c_void_p, u64p, ctypes.c_int64, i64p]
    lib.pwtpu_idx_lookup.restype = None
    lib.pwtpu_idx_remove.argtypes = [ctypes.c_void_p, u64p, ctypes.c_int64, i64p]
    lib.pwtpu_idx_remove.restype = None
    lib.pwtpu_idx_items.argtypes = [ctypes.c_void_p, u64p, i64p]
    lib.pwtpu_idx_items.restype = None
    lib.pwtpu_idx_restore.argtypes = [
        ctypes.c_void_p, u64p, i64p, ctypes.c_int64, ctypes.c_int64,
    ]
    lib.pwtpu_idx_restore.restype = None
    lib.pwtpu_mm_new.argtypes = []
    lib.pwtpu_mm_new.restype = ctypes.c_void_p
    lib.pwtpu_mm_free.argtypes = [ctypes.c_void_p]
    lib.pwtpu_mm_free.restype = None
    lib.pwtpu_mm_total.argtypes = [ctypes.c_void_p]
    lib.pwtpu_mm_total.restype = ctypes.c_int64
    lib.pwtpu_mm_insert.argtypes = [ctypes.c_void_p, u64p, i64p, ctypes.c_int64]
    lib.pwtpu_mm_insert.restype = None
    lib.pwtpu_mm_remove.argtypes = [ctypes.c_void_p, u64p, i64p, ctypes.c_int64, u8p]
    lib.pwtpu_mm_remove.restype = None
    lib.pwtpu_mm_count.argtypes = [ctypes.c_void_p, u64p, ctypes.c_int64, i64p]
    lib.pwtpu_mm_count.restype = ctypes.c_int64
    lib.pwtpu_mm_fill.argtypes = [ctypes.c_void_p, u64p, ctypes.c_int64, i64p]
    lib.pwtpu_mm_fill.restype = None
    lib.pwtpu_mm_items.argtypes = [ctypes.c_void_p, u64p, i64p]
    lib.pwtpu_mm_items.restype = None
    lib.pwtpu_side_insert.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, u64p, u64p, ctypes.c_int64,
        u64p, u64p, i64p,
    ]
    lib.pwtpu_side_insert.restype = None
    lib.pwtpu_side_remove.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, u64p, ctypes.c_int64, u64p, i64p,
    ]
    lib.pwtpu_side_remove.restype = None
    _lib = lib
    return _lib


class PwCol(ctypes.Structure):
    _fields_ = [
        ("kind", ctypes.c_int32),
        ("data", ctypes.c_void_p),
        ("offsets", ctypes.c_void_p),
        ("mask", ctypes.c_void_p),
    ]


def split_dsv(data: bytes, delimiter: str = ",") -> "list[list[str]] | None":
    """Split DSV content into rows of string fields natively; None if unavailable.

    Handles double-quote quoting with "" escapes and CRLF, mirroring the reference's
    Dsv parser (src/connectors/data_format.rs:500).
    """
    lib = get_lib()
    if lib is None:
        return None
    import numpy as np

    n = len(data)
    needed_bytes = ctypes.c_uint64()
    needed_fields = ctypes.c_uint64()
    delim = delimiter.encode()[:1]
    nrows = lib.pwtpu_split_dsv(
        data, n, delim, None, None, None, None,
        ctypes.byref(needed_bytes), ctypes.byref(needed_fields),
    )
    if nrows == 0:
        return []
    field_buf = ctypes.create_string_buffer(max(needed_bytes.value, 1))
    offsets = np.zeros(needed_fields.value + 1, dtype=np.uint64)
    counts = np.zeros(nrows, dtype=np.uint64)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.pwtpu_split_dsv(
        data, n, delim, field_buf,
        offsets.ctypes.data_as(u64p), counts.ctypes.data_as(u64p),
        None, None, None,
    )
    raw = field_buf.raw
    rows: list[list[str]] = []
    f = 0
    for r in range(nrows):
        k = int(counts[r])
        row = [
            raw[int(offsets[f + j]) : int(offsets[f + j + 1])].decode("utf-8", "replace")
            for j in range(k)
        ]
        f += k
        rows.append(row)
    return rows


def parse_dsv_rows(
    data: bytes,
    selected: "list[tuple[str, int]]",
    delimiter: str,
    error_obj: object,
) -> "list[dict] | None":
    """Fused native DSV parse → list of row dicts; None when unavailable.

    ``selected``: (column_name, tag) pairs; tag 0=str 1=int 2=float 3=bool. Name→column
    resolution happens natively against the file's (properly split) header row; wanted
    columns absent from the header are omitted from the rows, like DictReader.
    Malformed typed fields yield ``error_obj``.
    """
    lib = get_lib()
    if lib is None or len(delimiter.encode()) != 1:
        return None
    tags = (ctypes.c_int32 * len(selected))(*[tag for _name, tag in selected])
    names = tuple(name for name, _tag in selected)
    return lib.pwtpu_parse_dsv_rows(
        data, len(data), delimiter.encode(), names, tags, len(selected), error_obj
    )
