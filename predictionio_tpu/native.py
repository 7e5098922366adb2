"""ctypes bindings for the native data-plane kernels under ``native/``.

The compute plane is JAX/XLA; these kernels cover the *data* plane's
CPU-bound hot spots — currently the columnar JSON property scan behind
``parquet.promote_numeric`` (tens of millions of small JSON objects per
compaction, where per-row ``json.loads`` costs minutes).

Design rules:

* Pure C ABI loaded via ctypes (this image has no pybind11).
* The library is built lazily from ``native/*.cpp`` with ``g++`` the first
  time it is needed and cached beside the sources under a name that
  carries the source's digest, so a library built from any other source
  is never loaded; no compiler → the Python implementations are used
  (:func:`status` says which ran).
* Kernels are STRICT: anything surprising (malformed JSON, nulls,
  string-typed numerics) makes them decline the whole batch, and callers
  run their exact-semantics Python path instead. A kernel may be fast or
  absent, never subtly different.
* ``PIO_NATIVE=0`` disables all native kernels (env kill switch).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

logger = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native")
_SRC_PATH = os.path.join(_NATIVE_DIR, "jsonprops.cpp")

_lib = None
_lib_tried = False
_lib_lock = threading.Lock()


def _source_digest() -> Optional[str]:
    try:
        with open(_SRC_PATH, "rb") as f:
            return hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError:
        return None


def _so_path(digest: str) -> str:
    # the digest in the name is what ties a library to the committed
    # source: file times say nothing in a fresh checkout or a copied tree
    return os.path.join(_NATIVE_DIR, f"libpioprops-{digest}.so")


def _build(so_path: str) -> bool:
    """Compile the kernel library; True on success.

    Compiles to a per-process temp name and os.replace()s into place —
    concurrent first-use processes (multi-process scale-out is a supported
    topology) must never dlopen a half-written file.
    """
    gxx = os.environ.get("CXX") or "g++"
    tmp = f"{so_path}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            [gxx, "-O3", "-Wall", "-shared", "-fPIC", "-o", tmp, _SRC_PATH],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, so_path)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        logger.info("native kernel build unavailable (%s); using Python paths", e)
        try:
            os.remove(tmp)
        except OSError:
            pass
        return False


def load() -> Optional[ctypes.CDLL]:
    """The kernel library, building it on first use; None when unavailable."""
    global _lib, _lib_tried
    if _lib is not None or _lib_tried:
        return _lib
    with _lib_lock:
        if _lib is not None or _lib_tried:
            return _lib
        _lib_tried = True
        if os.environ.get("PIO_NATIVE", "1") == "0":
            return None
        digest = _source_digest()
        if digest is None:
            return None
        so_path = _so_path(digest)
        if not os.path.exists(so_path) and not _build(so_path):
            return None
        try:
            lib = ctypes.CDLL(so_path)
        except OSError as e:
            logger.info("native kernel load failed (%s); using Python paths", e)
            return None
        lib.pio_props_scan.restype = ctypes.c_void_p
        lib.pio_props_scan.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64,
        ]
        lib.pio_props_nkeys.restype = ctypes.c_int64
        lib.pio_props_nkeys.argtypes = [ctypes.c_void_p]
        lib.pio_props_key_name.restype = ctypes.c_char_p
        lib.pio_props_key_name.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.pio_props_key_flags.restype = ctypes.c_int32
        lib.pio_props_key_flags.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.pio_props_key_column.restype = ctypes.POINTER(ctypes.c_double)
        lib.pio_props_key_column.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.pio_props_free.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def status() -> dict:
    """Which implementation serves this process: ``{"loaded": bool,
    "source_sha256": digest-or-None}``.  ``loaded`` is True only for a
    library built from the source with that digest."""
    return {"loaded": load() is not None, "source_sha256": _source_digest()}


def scan_numeric_props(props) -> Optional[dict[str, np.ndarray]]:
    """Columnar float64 columns for promotable numeric property keys.

    ``props`` is a sequence of JSON-object strings (one per row). Returns
    {key: (nrows,) float64 array, NaN where absent} covering exactly the
    keys whose present values are all JSON numbers or booleans — the
    subset where C and Python coercion agree bit-for-bit. Keys with
    null/object/array values, or strings that provably cannot coerce with
    ``float`` (most labels/ids), are rejected exactly as the Python path
    rejects them. Returns None (caller must use its Python path) when the
    kernel is unavailable, any row fails to parse, any cell is null, or a
    string value MIGHT be float-coercible (e.g. ``"3"`` — Python's
    coercion semantics must decide).
    """
    lib = load()
    if lib is None:
        return None
    import pyarrow as pa

    try:
        # large_string = int64 offsets + one contiguous UTF-8 buffer: the
        # exact layout the C ABI takes, no per-row Python objects. The
        # sentinel "{}" row guarantees any malformed trailing number in the
        # last real row terminates inside the buffer.
        arr = pa.array(list(props) + ["{}"], type=pa.large_string())
    except (pa.ArrowInvalid, pa.ArrowTypeError, TypeError):
        return None
    if arr.null_count:
        return None
    _validity, offsets_buf, data_buf = arr.buffers()
    offsets = np.frombuffer(offsets_buf, dtype=np.int64)
    n = len(props)
    handle = lib.pio_props_scan(
        data_buf.address,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n,
    )
    if not handle:
        return None
    try:
        out: dict[str, np.ndarray] = {}
        for i in range(lib.pio_props_nkeys(handle)):
            flags = lib.pio_props_key_flags(handle, i)
            if flags & 1:  # saw a string value: Python coercion semantics
                return None
            if flags & 2:  # null/object/array: key is not promotable
                continue
            name = lib.pio_props_key_name(handle, i).decode("utf-8")
            col_ptr = lib.pio_props_key_column(handle, i)
            if not col_ptr:  # defensive: a clean key always has a column
                return None
            out[name] = np.ctypeslib.as_array(col_ptr, shape=(n,)).copy()
        return out
    finally:
        lib.pio_props_free(handle)
