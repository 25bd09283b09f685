"""L2: columnar file formats + per-file stats + secondary file indexes.

Capability parity with the reference format SPI
(/root/reference/paimon-common/.../format/FileFormat.java:41 — discovery via
identifier, createReaderFactory/createWriterFactory :59-63; impls in
paimon-format/: parquet, orc, avro) and SimpleStatsCollector/Extractor.

TPU-first decisions:
  * container parsing (parquet/orc structure, compression) stays on host via
    pyarrow's C++ readers — that path is already vectorized and feeds numpy
    buffers that transfer to device untouched;
  * per-file, per-field min/max/null-count stats are collected vectorized at
    write time and embedded in DataFileMeta for planner pruning;
  * predicate pushdown happens twice: row-group/stripe skipping inside the
    reader (host) and dense mask eval on the decoded batch (device-capable).
"""

from __future__ import annotations

from typing import Any, Callable, Iterator, Sequence

import numpy as np

from ..data.batch import ColumnBatch
from ..data.predicate import FieldStats, Predicate
from ..fs import FileIO
from ..types import RowType, TypeRoot

__all__ = [
    "FileFormat",
    "get_format",
    "register_format",
    "collect_stats",
    "stats_to_json",
    "stats_from_json",
]


class FileFormat:
    """A data file format: writes a ColumnBatch to one file, reads it back
    (with projection + predicate pushdown)."""

    identifier: str = "?"

    def configure(self, format_options: dict | None) -> "FileFormat":
        """Apply reader-side format options (e.g. format.parquet.decoder)
        to this instance; default is a no-op. Returns self for chaining."""
        return self

    def write(
        self,
        file_io: FileIO,
        path: str,
        batch: ColumnBatch,
        compression: str = "zstd",
        format_options: dict | None = None,
    ) -> None:
        raise NotImplementedError

    def read(
        self,
        file_io: FileIO,
        path: str,
        schema: RowType,
        projection: Sequence[str] | None = None,
        predicate: Predicate | None = None,
    ) -> Iterator[ColumnBatch]:
        raise NotImplementedError


_FORMATS: dict[str, Callable[[], FileFormat]] = {}


def register_format(identifier: str, factory: Callable[[], FileFormat]) -> None:
    _FORMATS[identifier] = factory


def get_format(identifier: str) -> FileFormat:
    if identifier not in _FORMATS:
        # lazy import of built-ins
        from . import avro, orc, parquet  # noqa: F401

    if identifier not in _FORMATS:
        raise ValueError(f"unknown file format {identifier!r}; known: {sorted(_FORMATS)}")
    return _FORMATS[identifier]()


# ---- stats ---------------------------------------------------------------

_TRUNCATE_LEN = 16


def collect_stats(batch: ColumnBatch, truncate: int = _TRUNCATE_LEN) -> dict[str, FieldStats]:
    """Vectorized per-field min/max/null-count (reference SimpleStatsCollector).
    String min/max are truncated to `truncate` chars (metadata.stats-mode
    truncate(16)): truncation keeps min a lower bound; the truncated max is
    bumped so it stays an upper bound."""
    out: dict[str, FieldStats] = {}
    n = batch.num_rows
    for f in batch.schema.fields:
        col = batch.column(f.name)
        nulls = col.null_count
        if nulls >= n or n == 0:
            out[f.name] = FieldStats(None, None, nulls, n)
            continue
        if f.type.root in (TypeRoot.ARRAY, TypeRoot.MAP, TypeRoot.ROW):
            # nested values have no total order: null-count-only stats
            out[f.name] = FieldStats(None, None, nulls, n)
            continue
        if f.type.numpy_dtype() == np.dtype(object):
            cache = getattr(col, "dict_cache", None)
            if cache is not None and len(cache[1]) == n:
                # key-lane pool reuse: the pool is sorted, so min/max are a
                # uint32 reduction over the (valid) ranks — no object
                # comparisons, and a code-backed column never expands
                pool, codes = cache
                if nulls:
                    codes = codes[col.validity]
                lo, hi = pool[int(codes.min())], pool[int(codes.max())]
            elif _arrow_orders_as_python(col.arrow):
                lo, hi = _arrow_min_max(col, nulls)
            else:
                v = col.values[col.valid_mask()] if nulls else col.values
                lo, hi = min(v), max(v)
            lo, hi = _truncate_min(lo, truncate), _truncate_max(hi, truncate)
            out[f.name] = FieldStats(lo, hi, nulls, n)
            continue
        v = col.values[col.valid_mask()] if nulls else col.values
        if v.dtype.kind == "f":
            # NaN-ignoring reductions: a NaN min/max would defeat every
            # stats comparison and prune files that contain matches
            with np.errstate(invalid="ignore"):
                lo, hi = np.nanmin(v), np.nanmax(v)
            if np.isnan(lo) or np.isnan(hi):
                out[f.name] = FieldStats(None, None, nulls, n)
                continue
            lo, hi = _to_py(lo), _to_py(hi)
        else:
            lo, hi = _to_py(v.min()), _to_py(v.max())
        out[f.name] = FieldStats(lo, hi, nulls, n)
    return out


def _arrow_orders_as_python(arr) -> bool:
    """An arrow-backed STRING or BINARY column: arrow compares the bytes, and
    UTF-8 byte order is code-point order, which is how Python compares str."""
    if arr is None:
        return False
    import pyarrow as pa

    t = arr.type
    return pa.types.is_string(t) or pa.types.is_large_string(t) or pa.types.is_binary(t) or pa.types.is_large_binary(t)


def _arrow_min_max(col, nulls: int):
    """min and max of an arrow-backed column in arrow's kernel, over the
    array the file is written from: no Python object a row (Column.values
    stays unmaterialized) and no interpreter lock held while it runs. Nulls
    are skipped; a validity kept beside the array that hides more than the
    array's own nulls is applied first."""
    import pyarrow as pa
    import pyarrow.compute as pc

    arr = col.arrow
    if nulls != arr.null_count:
        arr = arr.filter(pa.array(col.validity))
    mm = pc.min_max(arr)
    return mm["min"].as_py(), mm["max"].as_py()


def _to_py(x):
    return x.item() if hasattr(x, "item") else x


def _truncate_min(x, limit: int):
    if isinstance(x, (str, bytes)) and len(x) > limit:
        return x[:limit]
    return x


def _truncate_max(x, limit: int):
    if isinstance(x, str) and len(x) > limit:
        t = x[:limit]
        # bump last char so truncated value stays >= every original
        for i in range(len(t) - 1, -1, -1):
            if ord(t[i]) < 0x10FFFF:
                return t[:i] + chr(ord(t[i]) + 1)
        return x
    if isinstance(x, bytes) and len(x) > limit:
        t = bytearray(x[:limit])
        for i in range(len(t) - 1, -1, -1):
            if t[i] < 0xFF:
                t[i] += 1
                return bytes(t[: i + 1])
        return x
    return x


def stats_to_json(stats: dict[str, FieldStats]) -> dict:
    def enc(v):
        if isinstance(v, bytes):
            return {"b64": __import__("base64").b64encode(v).decode()}
        if isinstance(v, (bool, int, float, str)) or v is None:
            return v
        return str(v)

    return {
        name: {"min": enc(s.min), "max": enc(s.max), "nullCount": s.null_count, "rowCount": s.row_count}
        for name, s in stats.items()
    }


def stats_from_json(d: dict) -> dict[str, FieldStats]:
    def dec(v):
        if isinstance(v, dict) and "b64" in v:
            return __import__("base64").b64decode(v["b64"])
        return v

    return {
        name: FieldStats(dec(s["min"]), dec(s["max"]), s["nullCount"], s["rowCount"])
        for name, s in d.items()
    }
