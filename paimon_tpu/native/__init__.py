"""Native (C) accelerators, built on demand and loaded via ctypes.

The reference has no native code (its hot loops ride the JVM JIT); here the
device kernels are the main "native" layer, but row-major container formats
like Avro cannot be columnarized before parsing — so their inner decode loop
is C. Compiled once per source version into ``_build/`` with ``cc -O3
-shared -fPIC``; when no compiler is available every caller falls back to
the pure-python path, and a warning says so.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
import warnings

import numpy as np

__all__ = ["avro_decoder", "native_available"]

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD = os.path.join(_DIR, "_build")
_LOCK = threading.Lock()
_LIB: "ctypes.CDLL | None | bool" = None  # None = not tried, False = unavailable

# field type codes — must match avrodec.c
CODE_LONG = 0
CODE_FLOAT = 1
CODE_DOUBLE = 2
CODE_BOOL = 3
CODE_STRING = 4


def _load() -> "ctypes.CDLL | None":
    global _LIB
    with _LOCK:
        if _LIB is not None:
            return _LIB or None
        src = os.path.join(_DIR, "avrodec.c")
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:12]
        # keyed on the source's CONTENT: a copied checkout has fresh mtimes
        # and no _build/ (it is not in git), and an edited source must never
        # load a stale library
        so_path = os.path.join(_BUILD, f"avrodec-{digest}.so")
        try:
            if not os.path.exists(so_path):
                os.makedirs(_BUILD, exist_ok=True)
                # build to a private name, publish atomically: a concurrent
                # process must never dlopen a half-written library
                tmp_path = f"{so_path}.{os.getpid()}.tmp"
                subprocess.run(
                    ["cc", "-O3", "-shared", "-fPIC", "-o", tmp_path, src],
                    check=True,
                    capture_output=True,
                )
                os.replace(tmp_path, so_path)
            lib = ctypes.CDLL(so_path)
        except (OSError, subprocess.CalledProcessError) as e:
            detail = getattr(e, "stderr", b"") or b""
            warnings.warn(
                "paimon_tpu.native: avrodec.c did not build/load, Avro blocks decode "
                f"in Python: {e!r} {detail.decode(errors='replace')[-500:]}",
                RuntimeWarning,
                stacklevel=2,
            )
            _LIB = False
            return None
        lib.decode_block.restype = ctypes.c_int
        lib.encode_block.restype = ctypes.c_int64
        _LIB = lib
        return lib


def native_available() -> bool:
    return _load() is not None


def avro_decoder(payload: bytes, count: int, field_specs: list[tuple[int, bool]]):
    """Decode one Avro block natively.

    field_specs: [(type_code, nullable)] per field. Returns a list of
    per-field results or None if the native library is unavailable:
      numeric/bool: (values ndarray, validity ndarray)
      string:       (offsets int32 ndarray (n+1), data bytes, validity)
    """
    lib = _load()
    if lib is None:
        return None
    nfields = len(field_specs)
    type_codes = np.array([c for c, _ in field_specs], dtype=np.int32)
    nullable = np.array([1 if n else 0 for _, n in field_specs], dtype=np.uint8)
    num_out = (ctypes.c_void_p * nfields)()
    valid_out = (ctypes.POINTER(ctypes.c_uint8) * nfields)()
    str_offsets = (ctypes.POINTER(ctypes.c_int32) * nfields)()
    str_data = (ctypes.POINTER(ctypes.c_uint8) * nfields)()
    str_cap = np.zeros(nfields, dtype=np.int64)

    results: list = [None] * nfields
    n_strings = sum(1 for c, _ in field_specs if c == CODE_STRING)
    # the fields' combined string bytes cannot exceed the payload, but any
    # ONE field may own almost all of it: start with an even share + slack
    # and retry once with the full payload size on overflow (rc == -2)
    cap_guess = max(64, len(payload) // max(n_strings, 1) + 1024)
    for attempt in range(2):
        for f, (code, _) in enumerate(field_specs):
            validity = np.empty(count, dtype=np.uint8)
            valid_out[f] = validity.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
            if code == CODE_STRING:
                offsets = np.empty(count + 1, dtype=np.int32)
                data = np.empty(cap_guess, dtype=np.uint8)
                str_offsets[f] = offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
                str_data[f] = data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
                str_cap[f] = cap_guess
                results[f] = (offsets, data, validity)
            else:
                dtype = {CODE_LONG: np.int64, CODE_FLOAT: np.float64, CODE_DOUBLE: np.float64, CODE_BOOL: np.uint8}[code]
                values = np.empty(count, dtype=dtype)
                num_out[f] = values.ctypes.data_as(ctypes.c_void_p)
                results[f] = (values, validity)

        rc = lib.decode_block(
            payload,
            ctypes.c_size_t(len(payload)),
            ctypes.c_int64(count),
            ctypes.c_int(nfields),
            type_codes.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            nullable.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            num_out,
            valid_out,
            str_offsets,
            str_data,
            str_cap.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        )
        if rc == 0:
            return results
        if rc == -2 and attempt == 0:
            cap_guess = max(64, len(payload))  # one field owns most bytes
            continue
        return None  # malformed: python fallback handles it
    return None


def avro_encoder(count: int, field_specs: list[tuple[int, bool]], columns: list) -> bytes | None:
    """Encode one Avro block natively. `columns` mirrors avro_decoder's
    output shapes: numeric/bool -> (values ndarray, validity ndarray|None);
    string -> (offsets int32 ndarray, data uint8 ndarray, validity|None).
    Returns the block body bytes or None if unavailable."""
    lib = _load()
    if lib is None:
        return None
    nfields = len(field_specs)
    type_codes = np.array([c for c, _ in field_specs], dtype=np.int32)
    nullable = np.array([1 if n else 0 for _, n in field_specs], dtype=np.uint8)
    num_in = (ctypes.c_void_p * nfields)()
    valid_in = (ctypes.POINTER(ctypes.c_uint8) * nfields)()
    str_offsets = (ctypes.POINTER(ctypes.c_int32) * nfields)()
    str_data = (ctypes.POINTER(ctypes.c_uint8) * nfields)()
    keep = []
    cap = 64
    for f, (code, _) in enumerate(field_specs):
        col = columns[f]
        if code == CODE_STRING:
            offsets, data, validity = col
            offsets = np.ascontiguousarray(offsets, dtype=np.int32)
            data = np.ascontiguousarray(data, dtype=np.uint8)
            keep.extend([offsets, data])
            str_offsets[f] = offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
            str_data[f] = data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
            cap += len(data) + count * 12
        else:
            values, validity = col
            dtype = {CODE_LONG: np.int64, CODE_FLOAT: np.float64, CODE_DOUBLE: np.float64, CODE_BOOL: np.uint8}[code]
            values = np.ascontiguousarray(values, dtype=dtype)
            keep.append(values)
            num_in[f] = values.ctypes.data_as(ctypes.c_void_p)
            cap += count * 12
        if validity is None:
            valid_in[f] = ctypes.cast(None, ctypes.POINTER(ctypes.c_uint8))
        else:
            v = np.ascontiguousarray(validity, dtype=np.uint8)
            keep.append(v)
            valid_in[f] = v.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    out = np.empty(cap, dtype=np.uint8)
    n = lib.encode_block(
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.c_size_t(cap),
        ctypes.c_int64(count),
        ctypes.c_int(nfields),
        type_codes.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        nullable.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        num_in,
        valid_in,
        str_offsets,
        str_data,
    )
    if n < 0:
        return None
    return out[:n].tobytes()
