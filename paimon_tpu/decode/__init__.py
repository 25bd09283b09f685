"""Native vectorized Parquet page-decode subsystem.

Takes Paimon data files from raw bytes to device-ready ColumnBatches
without pyarrow's decoder on the hot path (SURVEY §7 stage 2; round-5
verdict: host-side decode is 66% of the pipeline and the one `partial`
format component). The layers:

  thrift.py    — compact-protocol parser (footer + page headers)
  container.py — footer model, chunk slicing, page iteration, codecs
  kernels.py   — vectorized decoders: bit-unpack, RLE/bit-packed hybrid,
                 PLAIN, DELTA_BINARY_PACKED, dictionary gather, levels →
                 validity (numpy engine + jittable JAX twins)
  pages.py     — page → (values, validity) assembly with page skipping
  pushdown.py  — compressed-domain predicates: chunk stats + dictionary
                 code sets decide which pages ever expand (LSM-OPD)

Entry point `read_native` mirrors `ParquetFormat.read`'s arrow semantics:
one ColumnBatch per row group, rows in file order, fixed-width nulls filled
with zeros, predicate used for skipping only in ways the caller's later
dense `predicate.eval` makes exact. Files needing features outside the
native envelope raise UnsupportedParquetFeature and the format falls back
to the arrow decoder per file (counter decode.files_fallback).

Surfaced behind the FileFormat registry as table option
`format.parquet.decoder = arrow | native` (default arrow).
"""

from __future__ import annotations

import time
from typing import Sequence

import numpy as np

from ..data.batch import Column, ColumnBatch
from ..data.predicate import Predicate
from ..fs import FileIO
from ..metrics import decode_metrics
from ..types import RowType
from .container import (
    UnsupportedParquetFeature,
    expected_physical_type,
    parse_footer,
)
from .pages import decode_chunk
from .pushdown import row_group_keep_mask

__all__ = ["read_native", "UnsupportedParquetFeature"]


def read_native(
    file_io: FileIO,
    path: str,
    schema: RowType,
    projection: Sequence[str] | None = None,
    predicate: Predicate | None = None,
    dict_domain: bool = False,
    pool_limit: int | None = None,
) -> list[ColumnBatch]:
    """Decode one parquet file natively: list of ColumnBatches (one per
    surviving row group) under `schema` projected to `projection`.

    dict_domain=True (merge.dict-domain): string/bytes chunks that are fully
    dictionary-encoded come back as CODE-BACKED columns — (sorted pool,
    uint32 codes) via one dictionary sort + one code gather, no string
    object per row — re-using the index runs the pushdown gate already
    decoded. Chunks outside the envelope (PLAIN pages, a dictionary past
    pool_limit) expand exactly as before, per chunk."""
    metrics = decode_metrics()
    cols = list(projection) if projection is not None else list(schema.field_names)
    read_schema = schema.project(cols)
    data = file_io.read_bytes(path)
    footer = parse_footer(data)
    for f in read_schema.fields:
        if f.name not in footer.column_names:
            raise UnsupportedParquetFeature(f"column {f.name!r} not in file")
    # logical-type envelope check up front (nested types never decode
    # natively); the physical-type check happens lazily in decode_chunk so
    # all-null chunks — whose physical type arrow picks arbitrarily — pass
    expected = {f.name: expected_physical_type(f.type) for f in read_schema.fields}
    out: list[ColumnBatch] = []
    for rg in footer.row_groups:
        for f in read_schema.fields:
            if rg.columns.get(f.name) is None:
                raise UnsupportedParquetFeature(f"row group missing column {f.name!r}")
        if rg.num_rows == 0:
            continue
        tp = time.perf_counter()
        code_cache: dict | None = {} if dict_domain else None
        keep = row_group_keep_mask(
            data, footer, rg, predicate, schema, metrics=metrics, code_cache=code_cache
        )
        metrics.histogram("pushdown_ms").update((time.perf_counter() - tp) * 1000)
        if keep is False:
            continue
        columns: dict[str, Column] = {}
        for f in read_schema.fields:
            if dict_domain:
                col = _code_domain_column(
                    data, rg, f, keep, pool_limit, code_cache, metrics
                )
                if col is not None:
                    columns[f.name] = col
                    continue
            values, validity = decode_chunk(
                data,
                rg.columns[f.name],
                f.type,
                rg.num_rows,
                keep=keep,
                metrics=metrics,
                expected_physical=expected[f.name],
            )
            if keep is not None:
                values = values[keep]
                validity = None if validity is None else validity[keep]
            if validity is not None and validity.all():
                validity = None
            columns[f.name] = Column(values, validity)
        out.append(ColumnBatch(read_schema, columns))
    metrics.counter("files_native").inc()
    return out


_STRING_ROOTS = None
_FIXED_CODE_ROOTS = None


def _code_domain_column(data, rg, f, keep, pool_limit, code_cache, metrics):
    """One chunk as a code-backed Column, or None for the expanded path.
    Covers dictionary-encoded BYTE_ARRAY chunks (string/bytes) and — ISSUE
    12 — fixed-width INT32/INT64 chunks (int/bigint/date/timestamp), whose
    sorted pools keep their native dtype so low-cardinality numeric join
    keys match in the code domain too."""
    global _STRING_ROOTS, _FIXED_CODE_ROOTS
    from ..decode.container import T_BYTE_ARRAY, T_INT32, T_INT64

    if _STRING_ROOTS is None:
        from ..types import TypeRoot

        _STRING_ROOTS = (TypeRoot.CHAR, TypeRoot.VARCHAR, TypeRoot.BINARY, TypeRoot.VARBINARY)
        _FIXED_CODE_ROOTS = {
            TypeRoot.TINYINT: T_INT32,
            TypeRoot.SMALLINT: T_INT32,
            TypeRoot.INT: T_INT32,
            TypeRoot.DATE: T_INT32,
            TypeRoot.TIME: T_INT32,
            TypeRoot.BIGINT: T_INT64,
            TypeRoot.TIMESTAMP: T_INT64,
            TypeRoot.TIMESTAMP_LTZ: T_INT64,
        }
    chunk = rg.columns[f.name]
    root = f.type.root
    if root in _STRING_ROOTS:
        if chunk.physical_type != T_BYTE_ARRAY:
            return None
    elif _FIXED_CODE_ROOTS.get(root) != chunk.physical_type:
        return None
    if not chunk.has_dictionary:
        return None
    from ..metrics import dict_metrics
    from ..ops.dicts import remap_codes, resolve_pool_limit, sort_dictionary
    from .pages import chunk_codes

    g = dict_metrics()
    got = chunk_codes(
        data, chunk, f.type, rg.num_rows, keep=keep,
        metrics=metrics, reuse=(code_cache or {}).get(f.name),
    )
    if got is None:
        g.counter("fallback_expanded").inc(rg.num_rows)
        return None
    dictionary, codes, validity = got
    if root not in _STRING_ROOTS:
        np_dtype = f.type.numpy_dtype()
        if dictionary.dtype != np_dtype:
            dictionary = dictionary.astype(np_dtype, copy=False)
    if len(dictionary) > resolve_pool_limit(pool_limit):
        g.counter("fallback_expanded").inc(rg.num_rows)
        return None
    pool, remap = sort_dictionary(dictionary)
    codes = remap_codes(remap, codes)
    if keep is not None:
        codes = codes[keep]
        validity = None if validity is None else validity[keep]
    if validity is not None and validity.all():
        validity = None
    g.counter("rows_code_domain").inc(len(codes))
    return Column.from_codes(pool, codes, validity)
