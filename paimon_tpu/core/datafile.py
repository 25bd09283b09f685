"""Data file metadata + the keyed read/write plumbing.

Parity: /root/reference/paimon-core/.../io/ —
  DataFileMeta.java:54-109 (fileName, size, rowCount, minKey/maxKey,
  keyStats/valueStats, seq range, schemaId, level, deleteRowCount, fileSource),
  KeyValueDataFileWriter (stats collection), RollingFileWriter (target-size
  rolling), KeyValueFileReaderFactory.java:63 (format reader + schema
  evolution mapping + projection/predicate pushdown).
"""

from __future__ import annotations

import base64
from dataclasses import dataclass, field, replace
from typing import Iterator, Sequence

import numpy as np

from ..data.batch import Column, ColumnBatch
from ..data.casting import cast_column
from ..data.predicate import FieldStats, Predicate
from ..format import collect_stats, get_format, stats_from_json, stats_to_json
from ..fs import FileIO
from ..metrics import datafile_metrics, span
from ..types import DataField, RowKind, RowType
from ..utils import new_file_name, now_millis, on_shared_pool
from .kv import SEQUENCE_FIELD_NAME, VALUE_KIND_FIELD_NAME, KVBatch, kv_disk_schema

__all__ = ["DataFileMeta", "KeyValueFileWriterFactory", "KeyValueFileReaderFactory"]


@dataclass(frozen=True)
class DataFileMeta:
    file_name: str
    file_size: int
    row_count: int
    min_key: tuple  # first key tuple (file rows are key-sorted)
    max_key: tuple
    key_stats: dict[str, FieldStats]
    value_stats: dict[str, FieldStats]
    min_sequence_number: int
    max_sequence_number: int
    schema_id: int
    level: int
    delete_row_count: int = 0
    creation_time_millis: int = 0
    file_source: str = "append"  # append | compact
    extra_files: tuple[str, ...] = ()
    embedded_index: bytes | None = None  # small PTIX payload carried in the manifest

    def upgrade(self, level: int) -> "DataFileMeta":
        return replace(self, level=level)

    def to_dict(self) -> dict:
        return {
            "fileName": self.file_name,
            "fileSize": self.file_size,
            "rowCount": self.row_count,
            "minKey": list(self.min_key),
            "maxKey": list(self.max_key),
            "keyStats": stats_to_json(self.key_stats),
            "valueStats": stats_to_json(self.value_stats),
            "minSequenceNumber": self.min_sequence_number,
            "maxSequenceNumber": self.max_sequence_number,
            "schemaId": self.schema_id,
            "level": self.level,
            "deleteRowCount": self.delete_row_count,
            "creationTimeMillis": self.creation_time_millis,
            "fileSource": self.file_source,
            "extraFiles": list(self.extra_files),
            # base64 so the meta stays JSON-serializable (reference
            # DataFileMeta.embeddedIndex, file-index.in-manifest-threshold)
            "embeddedIndex": (
                None
                if self.embedded_index is None
                else base64.b64encode(self.embedded_index).decode()
            ),
        }

    @staticmethod
    def from_dict(d: dict) -> "DataFileMeta":
        return DataFileMeta(
            d["fileName"],
            d["fileSize"],
            d["rowCount"],
            tuple(d["minKey"]),
            tuple(d["maxKey"]),
            stats_from_json(d["keyStats"]),
            stats_from_json(d["valueStats"]),
            d["minSequenceNumber"],
            d["maxSequenceNumber"],
            d["schemaId"],
            d["level"],
            d.get("deleteRowCount", 0),
            d.get("creationTimeMillis", 0),
            d.get("fileSource", "append"),
            tuple(d.get("extraFiles", ())),
            (
                None
                if d.get("embeddedIndex") is None
                else base64.b64decode(d["embeddedIndex"])
            ),
        )


def _key_tuple(batch: ColumnBatch, key_names: Sequence[str], row: int) -> tuple:
    # value_at: two boundary rows must not expand a code-backed column
    return tuple(batch.column(k).value_at(row) for k in key_names)


def _to_py_tuple(t: tuple) -> tuple:
    return tuple(x.item() if hasattr(x, "item") else x for x in t)


class KeyValueFileWriterFactory:
    """Writes key-sorted KVBatches as data files with stats + optional bloom
    index sidecars."""

    def __init__(
        self,
        file_io: FileIO,
        bucket_dir: str,
        value_schema: RowType,
        key_names: Sequence[str],
        schema_id: int,
        file_format: str = "parquet",
        compression: str = "zstd",
        target_file_size: int = 128 << 20,
        bloom_columns: Sequence[str] = (),
        bloom_fpp: float = 0.05,
        key_bloom: bool = False,
        key_bloom_fpp: float = 0.001,
        index_in_manifest_threshold: int = 500,
        keyed: bool = True,
        format_options: dict | None = None,
        include_key_columns: bool = False,
        per_level_format: dict[int, str] | None = None,
        per_level_compression: dict[int, str] | None = None,
        parallelism: int | None = None,
    ):
        self.file_io = file_io
        self.bucket_dir = bucket_dir
        self.value_schema = value_schema
        self.key_names = list(key_names)
        self.schema_id = schema_id
        self.format_id = file_format
        self.compression = compression
        self.target_file_size = target_file_size
        self.bloom_columns = list(bloom_columns)
        self.bloom_fpp = bloom_fpp
        # composite primary-key bloom (file-index.bloom-filter.primary-key.
        # enabled): written at flush AND compaction time — both routes land
        # here — so the batched get path can prune any file without data IO
        self.key_bloom = bool(key_bloom) and keyed and bool(key_names)
        self.key_bloom_fpp = key_bloom_fpp
        self.index_in_manifest_threshold = index_in_manifest_threshold
        # keyed=False: append-only tables — plain rows on disk, no
        # _SEQUENCE_NUMBER/_VALUE_KIND columns, no key range
        # (reference AppendOnlyFileStore / AppendOnlyWriter)
        self.keyed = keyed
        self.format_options = format_options or {}
        # reference-layout data files: duplicate trimmed PK as _KEY_ columns
        self.include_key_columns = include_key_columns
        # per-LSM-level overrides (reference file.format.per.level /
        # file.compression.per.level); readers pick the format off the file
        # extension, so levels can mix freely
        self.per_level_format = per_level_format or {}
        self.per_level_compression = per_level_compression or {}
        # scan.parallelism: how many files of one write may be on the shared
        # pool at once (None = the pool's width, 1 = written in turn)
        self.parallelism = parallelism

    def _estimate_row_bytes(self, batch: ColumnBatch) -> int:
        total = 0
        for f in batch.schema.fields:
            dt = f.type.numpy_dtype()
            if dt == np.dtype(object):
                total += 16  # rough var-len average pre-compression
            else:
                total += dt.itemsize
        return max(total, 1)

    def write(
        self, kv: KVBatch, level: int, file_source: str = "append", prefix: str = "data",
        sorted_input: bool = True, measured_row_bytes: float | None = None,
    ) -> list[DataFileMeta]:
        """Rolls into multiple files at target size. Input must be key-sorted
        unless sorted_input=False (changelog files preserve event order; key
        min/max are then computed instead of taken from the edges).
        measured_row_bytes overrides the schema-based width estimate (callers
        with skewed var-length data pass actual bytes — the reference's
        sort-compaction.range-strategy=size)."""
        n = kv.num_rows
        if n == 0:
            return []
        row_bytes = measured_row_bytes or self._estimate_row_bytes(kv.data)
        rows_per_file = max(1, int(self.target_file_size / max(row_bytes, 1)))
        format_id = self.per_level_format.get(level, self.format_id)
        starts = range(0, n, rows_per_file)
        failures: list[BaseException] = []

        def write_part(start: int) -> DataFileMeta | None:
            if failures:  # a file before this one failed: write no more, as the loop in turn stops
                return None
            part = kv.slice(start, min(start + rows_per_file, n))
            try:
                with span("file.write", level=level, format=format_id, rows=part.num_rows) as sp:
                    meta = self._write_one(part, level, file_source, prefix, sorted_input)
                    sp.add(bytes=meta.file_size)
                    # what the write materialized on the part's columns is released here, inside the
                    # file's span, not between two files where no span names the time
                    del part
                return meta
            except BaseException as e:  # noqa: BLE001 — raised below, once no file is being written any more
                failures.append(e)
                return None

        # the files are independent (disjoint slices of one batch, a uuid name each): several go to the
        # shared pool, a file a task, unless this thread is one of the pool's (a pool task submits nothing)
        on_pool = len(starts) > 1 and (self.parallelism is None or self.parallelism > 1) and not on_shared_pool()
        if len(starts) == 1:
            out = [write_part(0)]
        else:
            from ..parallel.pipeline import bounded_map

            with span("files.write", files=len(starts), rows=n, on_pool=int(on_pool)):
                out = bounded_map(write_part, starts, self.parallelism if on_pool else 1)
        if failures:
            raise failures[0]
        g = datafile_metrics()
        g.counter("files_written").inc(len(out))
        if on_pool:
            g.counter("files_written_on_pool").inc(len(out))
        return out

    def _key_min_max(self, batch: ColumnBatch, sorted_input: bool) -> tuple[tuple, tuple]:
        if not self.key_names:
            return (), ()
        if sorted_input:
            return (
                _to_py_tuple(_key_tuple(batch, self.key_names, 0)),
                _to_py_tuple(_key_tuple(batch, self.key_names, batch.num_rows - 1)),
            )
        from ..ops.dicts import cache_usable

        def sort_key(k):
            col = batch.column(k)
            # codes are rank-order-preserving surrogates: the lexsort
            # permutation's first/last rows match the expanded sort exactly
            return col.dict_cache[1] if cache_usable(col) and col.validity is None else col.values

        order = np.lexsort([sort_key(k) for k in reversed(self.key_names)])
        return (
            _to_py_tuple(_key_tuple(batch, self.key_names, int(order[0]))),
            _to_py_tuple(_key_tuple(batch, self.key_names, int(order[-1]))),
        )

    def _write_one(
        self, kv: KVBatch, level: int, file_source: str, prefix: str = "data", sorted_input: bool = True
    ) -> DataFileMeta:
        format_id = self.per_level_format.get(level, self.format_id)
        compression = self.per_level_compression.get(level, self.compression)
        fmt = get_format(format_id)
        name = new_file_name(prefix, format_id)
        path = f"{self.bucket_dir}/{name}"
        key_cols = self.key_names if (self.keyed and self.include_key_columns) else None
        disk = kv.to_disk_batch(key_cols) if self.keyed else kv.data
        fmt.write(self.file_io, path, disk, compression, format_options=self.format_options)
        extra: list[str] = []
        embedded: bytes | None = None
        if self.bloom_columns or self.key_bloom:
            from ..format.fileindex import build_index_payload, index_path

            hashes = None
            if self.key_bloom:
                from ..table.bucket import key_hashes

                hashes = key_hashes(kv.data, self.key_names)
            payload = build_index_payload(
                kv.data, self.bloom_columns, self.bloom_fpp,
                key_hashes=hashes, key_fpp=self.key_bloom_fpp,
            )
            if payload is not None:
                if len(payload) <= self.index_in_manifest_threshold:
                    # small index rides in the manifest entry: zero extra
                    # opens per file per scan (reference in-manifest-threshold)
                    embedded = payload
                else:
                    self.file_io.write_bytes(index_path(path), payload, overwrite=True)
                    extra.append(name + ".index")
        value_stats = collect_stats(kv.data)
        key_stats = {k: value_stats[k] for k in self.key_names}
        delete_rows = int(np.isin(kv.kind, (int(RowKind.DELETE),)).sum())
        return DataFileMeta(
            file_name=name,
            file_size=self.file_io.get_status(path).size,
            row_count=kv.num_rows,
            min_key=self._key_min_max(kv.data, sorted_input)[0] if self.keyed else (),
            max_key=self._key_min_max(kv.data, sorted_input)[1] if self.keyed else (),
            key_stats=key_stats,
            value_stats=value_stats,
            min_sequence_number=int(kv.seq.min()),
            max_sequence_number=int(kv.seq.max()),
            schema_id=self.schema_id,
            level=level,
            delete_row_count=delete_rows,
            creation_time_millis=now_millis(),
            file_source=file_source,
            extra_files=tuple(extra),
            embedded_index=embedded,
        )


class KeyValueFileReaderFactory:
    """Reads data files back into KVBatches, applying field-id based schema
    evolution (reference SchemaEvolutionUtil.createIndexMapping:78): each
    field of the read schema is located in the file's write schema by id —
    missing => null column, type change => vectorized cast."""

    def __init__(
        self,
        file_io: FileIO,
        bucket_dir: str,
        read_schema: RowType,
        schemas_by_id: dict[int, RowType],
        file_format: str = "parquet",
        keyed: bool = True,
        cache=None,
        format_options: dict | None = None,
    ):
        self.file_io = file_io
        self.bucket_dir = bucket_dir
        self.read_schema = read_schema
        self.schemas_by_id = schemas_by_id
        self.format_id = file_format
        self.keyed = keyed
        # utils.cache data-file cache: data files are immutable, so fully
        # decoded (schema-evolved, cast) KVBatches are cached keyed by
        # (file, projection, system-columns mode, read-field signature,
        # decoder identity). Only predicate-FREE reads participate —
        # predicate pushdown skips row groups/pages, changing the row set
        # per predicate. Cached batches are shared: callers must never
        # mutate column arrays in place (the read path is copy-on-filter
        # throughout).
        self.cache = cache
        # reader-side format options (format.parquet.decoder etc.), applied
        # to the format instance via FileFormat.configure per read
        self.format_options = dict(format_options or {})
        # the dict-domain flag joins the decoder identity: a code-backed
        # batch must never alias an expanded one in the data-file cache
        # (switching merge.dict-domain or its env override stays sound)
        from ..ops.dicts import resolve_dict_domain

        decoder = str(self.format_options.get("format.parquet.decoder") or "arrow")
        if resolve_dict_domain(self.format_options.get("merge.dict-domain")):
            decoder += "+dict"
        self.decoder_id = decoder

    def read(
        self,
        meta: DataFileMeta,
        predicate: Predicate | None = None,
        fields: Sequence[str] | None = None,
        system_columns: bool | str = True,
    ) -> KVBatch:
        """fields: optional subset of read-schema fields to materialize (the
        returned KVBatch's data schema is projected accordingly). Row-group
        skipping depends only on `predicate`, so two reads of the same file
        with the same predicate but different `fields` are row-aligned —
        the pipelined merge path relies on that.

        system_columns: True reads _SEQUENCE_NUMBER + _VALUE_KIND; "kind"
        reads only _VALUE_KIND (seq zeros) — the keys-only merge pipeline
        uses it when run stability replaces sequence comparison, skipping
        the most expensive system column (random int64, ~uncompressible);
        False decodes neither (caller holds them from the key pass)."""
        if not self.keyed:
            system_columns = False
        if predicate is None and self.cache is not None and self.cache.enabled:
            read_names = self.read_schema.field_names if fields is None else list(fields)
            # the read-field signature pins projection AND schema evolution:
            # the same file re-read after an ALTER maps/casts differently
            sig = tuple((f.id, f.name, repr(f.type)) for f in (self.read_schema.field(n) for n in read_names))
            # decoder identity is part of the key: a batch decoded by the
            # arrow backend must never alias one the native backend would
            # produce (switching format.parquet.decoder stays sound).
            # Content-addressed, NOT path-addressed: file names are
            # uuid-unique, so the same file read through another factory —
            # a branch view, a rescale rewrite over a table copy — is a
            # cache hit instead of a cold re-decode.
            key = ("data", meta.file_name, system_columns, sig, fields is None, self.decoder_id)
            return self.cache.get_or_load(
                key,
                lambda: self._decode(meta, None, fields, system_columns),
                lambda kv: kv.byte_size(),
                file_id=meta.file_name,
            )
        return self._decode(meta, predicate, fields, system_columns)

    def _decode(
        self,
        meta: DataFileMeta,
        predicate: Predicate | None,
        fields: Sequence[str] | None,
        system_columns: bool | str,
    ) -> KVBatch:
        which = "all" if fields is None else ("values" if system_columns is False else "keys")
        with span("decode.file", format=meta.file_name.rsplit(".", 1)[-1], **{"pass": which}) as sp:
            kv = self._decode_file(meta, predicate, fields, system_columns)
            nbytes = kv.byte_size()
            sp.add(columns=len(kv.data.schema.fields), rows=kv.num_rows, bytes=nbytes)
        g = datafile_metrics()
        g.counter("files_decoded").inc()
        g.counter("rows_decoded").inc(kv.num_rows)
        g.counter("bytes_decoded").inc(nbytes)
        return kv

    def _decode_file(
        self,
        meta: DataFileMeta,
        predicate: Predicate | None,
        fields: Sequence[str] | None,
        system_columns: bool | str,
    ) -> KVBatch:
        data_schema = self.schemas_by_id[meta.schema_id]
        disk_schema = kv_disk_schema(data_schema) if self.keyed else data_schema
        read_fields = (
            self.read_schema.fields
            if fields is None
            else tuple(self.read_schema.field(n) for n in fields)
        )
        # project to the file columns that exist for the read schema
        by_id = {f.id: f for f in data_schema.fields}
        if system_columns is True:
            wanted_cols = [SEQUENCE_FIELD_NAME, VALUE_KIND_FIELD_NAME]
        elif system_columns == "kind":
            wanted_cols = [VALUE_KIND_FIELD_NAME]
        else:
            wanted_cols = []
        mapping: list[tuple[DataField, DataField | None]] = []
        for f in read_fields:
            src = by_id.get(f.id)
            mapping.append((f, src))
            if src is not None:
                wanted_cols.append(src.name)
        # the extension is authoritative: per-level format overrides mean a
        # table legitimately mixes formats across files
        ext = meta.file_name.rsplit(".", 1)[-1]
        fmt = get_format(ext if "." in meta.file_name else self.format_id).configure(self.format_options)
        path = f"{self.bucket_dir}/{meta.file_name}"
        parts = list(fmt.read(self.file_io, path, disk_schema, projection=wanted_cols, predicate=predicate))
        if parts:
            from ..data.batch import concat_batches

            disk = concat_batches(parts)
        else:
            disk = ColumnBatch.empty(disk_schema.project(wanted_cols))
        n = disk.num_rows
        cols: dict[str, Column] = {}
        for f, src in mapping:
            if src is None:
                cols[f.name] = Column(
                    np.zeros(n, dtype=f.type.numpy_dtype()) if f.type.numpy_dtype() != np.dtype(object) else np.full(n, None, dtype=object),
                    np.zeros(n, dtype=np.bool_),
                )
            else:
                col = disk.column(src.name)
                cols[f.name] = cast_column(col, src.type, f.type) if src.type != f.type else col
        out_schema = self.read_schema if fields is None else RowType(read_fields)
        data = ColumnBatch(out_schema, cols)
        if system_columns is True:
            seq = disk.column(SEQUENCE_FIELD_NAME).values.astype(np.int64, copy=False)
            kind = disk.column(VALUE_KIND_FIELD_NAME).values.astype(np.uint8)
        elif system_columns == "kind":
            seq = np.zeros(n, dtype=np.int64)
            kind = disk.column(VALUE_KIND_FIELD_NAME).values.astype(np.uint8)
        else:  # caller already holds seq/kind from the key pass
            seq = np.zeros(n, dtype=np.int64)
            kind = np.zeros(n, dtype=np.uint8)
        return KVBatch(data, seq, kind)
