"""Parquet read/write over pyarrow, with row-group predicate skipping and an
optional native page-decode backend.

Parity: /root/reference/paimon-format/.../parquet/ParquetReaderFactory.java:68
(vectorized batch decode, row-group filtering via FilterCompat) and
ParquetRowDataWriter. Two read decoders sit behind one `read()`:

  * arrow (default)  — the C++ arrow reader decodes columns into numpy
    buffers; row-group pruning reuses Predicate.test_stats fed from parquet
    footer statistics;
  * native           — paimon_tpu.decode: thrift-parsed footer/pages,
    vectorized RLE/dict/delta kernels, and compressed-domain predicate
    pushdown that expands only surviving pages. Selected per table via
    `format.parquet.decoder = native`; files needing features outside the
    native envelope (nested schemas, exotic encodings) fall back to arrow
    per file (counter decode.files_fallback).

Two write encoders sit behind one `write()` the same way:

  * arrow (default)  — ColumnBatch.to_arrow (per-column pa.array object
    conversion) into pq.write_table;
  * native           — paimon_tpu.encode: vectorized PLAIN/RLE/DELTA/
    dictionary kernels writing pages straight from columnar arrays, with
    dictionary pages consuming the merge path's string pools directly.
    Selected per table via `format.parquet.encoder = native`; unsupported
    shapes fall back to arrow per file (counter encode.files_fallback),
    never per table.
"""

from __future__ import annotations

from typing import Iterator, Sequence

import numpy as np

from ..data.batch import ColumnBatch
from ..data.predicate import FieldStats, Predicate
from ..fs import FileIO
from ..metrics import span
from ..types import RowType, TypeRoot
from . import FileFormat, from_arrow, library_call, put, register_format, to_arrow

_OBJ_DTYPE = np.dtype(object)
_STRING_ROOTS = (TypeRoot.CHAR, TypeRoot.VARCHAR, TypeRoot.BINARY, TypeRoot.VARBINARY)


class ParquetFormat(FileFormat):
    identifier = "parquet"

    def __init__(self, decoder: str = "arrow", encoder: str = "arrow"):
        self.decoder = decoder
        self.encoder = encoder
        # merge.dict-domain: both decoders return dictionary-encoded
        # string/bytes chunks as code-backed columns (PAIMON_TPU_DICT_DOMAIN
        # env overrides, same rollout pattern as the decoder/encoder knobs)
        from ..ops.dicts import resolve_dict_domain, resolve_pool_limit

        self.dict_domain = resolve_dict_domain(None)
        self.pool_limit = resolve_pool_limit(None)

    def configure(self, format_options: dict | None) -> "ParquetFormat":
        from ..ops.dicts import resolve_dict_domain, resolve_pool_limit

        opts = format_options or {}
        d = opts.get("format.parquet.decoder")
        if d:
            self.decoder = str(d)
        e = opts.get("format.parquet.encoder")
        if e:
            self.encoder = str(e)
        self.dict_domain = resolve_dict_domain(opts.get("merge.dict-domain"))
        self.pool_limit = resolve_pool_limit(opts.get("merge.dict-domain.pool-limit"))
        return self

    def _effective_encoder(self, format_options: dict | None) -> str:
        # PAIMON_TPU_PARQUET_ENCODER lets scripts/verify.sh force the whole
        # suite through one encoder (same pattern as the pipeline stage's
        # PAIMON_TPU_SCAN_PARALLELISM)
        import os

        env = os.environ.get("PAIMON_TPU_PARQUET_ENCODER")
        if env:
            return env
        return str((format_options or {}).get("format.parquet.encoder") or self.encoder)

    def write(
        self,
        file_io: FileIO,
        path: str,
        batch: ColumnBatch,
        compression: str = "zstd",
        format_options: dict | None = None,
    ) -> None:
        import io as _io

        import pyarrow.parquet as pq

        if self._effective_encoder(format_options) == "native":
            from ..decode.container import UnsupportedParquetFeature
            from ..encode import write_native

            try:
                write_native(file_io, path, batch, compression, format_options)
                return
            except UnsupportedParquetFeature:
                # per-FILE fallback: this batch needs a feature outside the
                # native envelope (nested columns, exotic codec); later
                # files still try the native path
                from ..metrics import encode_metrics

                encode_metrics().counter("files_fallback").inc()

        table = to_arrow(batch)
        buf = _io.BytesIO()
        opts = format_options or {}
        kw = {}
        if "parquet.row-group.rows" in opts:
            kw["row_group_size"] = int(opts["parquet.row-group.rows"])
        elif "file.block-size" in opts and table.num_rows:
            # block-size is bytes; pyarrow sizes row groups in rows —
            # translate through the actual in-memory bytes/row of this table
            per_row = max(1, table.nbytes // table.num_rows)
            kw["row_group_size"] = max(1024, int(opts["file.block-size"]) // per_row)
        if "parquet.enable.dictionary" in opts:
            kw["use_dictionary"] = str(opts["parquet.enable.dictionary"]).lower() == "true"
        if "parquet.page-size" in opts:
            # smaller pages = finer native-decoder pushdown granularity
            kw["data_page_size"] = int(opts["parquet.page-size"])
        if "parquet.data-page-version" in opts:
            kw["data_page_version"] = str(opts["parquet.data-page-version"])
        if compression == "zstd" and "file.compression.zstd-level" in opts:
            kw["compression_level"] = int(opts["file.compression.zstd-level"])
        with span("encode.format", rows=batch.num_rows):
            pq.write_table(table, buf, compression=compression, **kw)
        put(file_io, path, buf, batch.num_rows)

    def read(
        self,
        file_io: FileIO,
        path: str,
        schema: RowType,
        projection: Sequence[str] | None = None,
        predicate: Predicate | None = None,
    ) -> Iterator[ColumnBatch]:
        import pyarrow.parquet as pq

        cols = list(projection) if projection is not None else schema.field_names
        read_schema = schema.project(cols)
        if self.decoder == "native":
            with library_call("native", len(cols)) as sp:
                batches = self._read_native(file_io, path, schema, cols, predicate)
                sp.add(rows=sum(b.num_rows for b in batches or ()))
            if batches is not None:
                # fully materialized before the first yield, so an
                # unsupported feature can still fall back without
                # double-emitting rows
                for b in batches:
                    if b.num_rows:
                        yield b
                return
        # prefer a real OS path: pyarrow then memory-maps and reads through
        # its own C++ IO instead of a Python-file shim (which is both slower
        # and flaky under concurrent threaded decode — see FileIO.local_path)
        lp = file_io.local_path(path)
        f = lp if lp is not None else file_io.open_input(path)
        pf = None
        kw = {}
        if self.dict_domain:
            # merge.dict-domain through the ARROW decoder: ask arrow to keep
            # string/bytes columns dictionary-encoded — from_arrow then
            # populates the code domain in one C pass per chunk, so the
            # compressed merge fires regardless of decoder choice
            kw["read_dictionary"] = [
                n for n in cols if read_schema.field(n).type.root in _STRING_ROOTS
            ]
        try:
            with library_call("open", len(cols), rows=0):
                try:
                    pf = pq.ParquetFile(f, memory_map=True, **kw)
                except (KeyError, OSError, ValueError):
                    if not kw:
                        raise
                    # a requested dictionary column isn't a plain leaf in this
                    # file (e.g. a collect aggregate stored the STRING field as
                    # a list) — read it expanded like before
                    kw = {}
                    pf = pq.ParquetFile(f, memory_map=True)
                md = pf.metadata
            name_to_idx = {md.schema.column(i).name: i for i in range(md.num_columns)}
            keep = [
                rg
                for rg in range(md.num_row_groups)
                if predicate is None
                or predicate.test_stats(
                    _row_group_stats(md, rg, name_to_idx, predicate.referenced_fields(), schema)
                )
            ]
            # batch consecutive groups into one read call (pyarrow decodes
            # columns and groups in parallel internally, where a
            # group-at-a-time loop is single-threaded per step) — but bound
            # each call's uncompressed bytes so a multi-GB file still
            # streams instead of materializing whole
            budget = 256 << 20
            i = 0
            while i < len(keep):
                chunk = [keep[i]]
                spent = md.row_group(keep[i]).total_byte_size
                i += 1
                while i < len(keep) and spent + md.row_group(keep[i]).total_byte_size <= budget:
                    spent += md.row_group(keep[i]).total_byte_size
                    chunk.append(keep[i])
                    i += 1
                with library_call("row_groups", len(cols)) as sp:
                    table = pf.read_row_groups(chunk, columns=cols)
                    sp.add(rows=table.num_rows)
                if table.num_rows:
                    yield from_arrow(table, read_schema)
        finally:
            if lp is None:
                f.close()
            elif pf is not None:
                pf.close()

    def _read_native(self, file_io, path, schema, cols, predicate):
        """Native decode of one file, or None to fall back to arrow."""
        from ..decode import UnsupportedParquetFeature, read_native

        try:
            return read_native(
                file_io,
                path,
                schema,
                projection=cols,
                predicate=predicate,
                dict_domain=self.dict_domain,
                pool_limit=self.pool_limit,
            )
        except UnsupportedParquetFeature:
            from ..metrics import decode_metrics

            decode_metrics().counter("files_fallback").inc()
            return None


def _row_group_stats(
    md, rg: int, name_to_idx: dict, fields: set[str], schema: RowType
) -> dict[str, FieldStats]:
    out: dict[str, FieldStats] = {}
    group = md.row_group(rg)
    for name in fields:
        idx = name_to_idx.get(name)
        if idx is None or name not in schema:
            continue
        col = group.column(idx)
        st = col.statistics
        if st is None or not st.has_min_max:
            continue
        # unknown null count must not prune null predicates
        nulls = st.null_count if st.has_null_count else None
        dtype = schema.field(name).type
        out[name] = FieldStats(
            _normalize_stat(st.min, dtype), _normalize_stat(st.max, dtype), nulls, group.num_rows
        )
    return out


def _normalize_stat(v, dtype):
    """Map arrow-logical stat values (datetime/date/Decimal) onto the internal
    physical representation that predicate literals use (micros / days /
    unscaled int64), mirroring ColumnBatch.from_arrow's normalization."""
    import datetime
    import decimal

    if v is None:
        return None
    if isinstance(v, datetime.datetime):
        epoch = datetime.datetime(1970, 1, 1, tzinfo=v.tzinfo)
        return int((v - epoch).total_seconds() * 1_000_000)
    if isinstance(v, datetime.date):
        return (v - datetime.date(1970, 1, 1)).days
    if isinstance(v, decimal.Decimal):
        scale = dtype.scale or 0
        return int(v.scaleb(scale))
    return v


register_format("parquet", ParquetFormat)
