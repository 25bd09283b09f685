"""KeyValueFileStore: the facade wiring scan/read/write/commit together.

Parity: /root/reference/paimon-core/.../FileStore.java:53 (newScan/newRead/
newWrite/newCommit) and KeyValueFileStore.java:62 (+ KeyValueFileStoreWrite.
createWriter :165-219 wiring memtable + compaction, restore from the latest
snapshot). Directory layout mirrors the reference:
  table/schema/schema-N
  table/snapshot/snapshot-N (+ LATEST/EARLIEST hints)
  table/manifest/{manifest-*,manifest-list-*}
  table/[k1=v1/k2=v2/]bucket-B/data-*.parquet
"""

from __future__ import annotations

from typing import Sequence

from ..fs import FileIO
from ..options import CoreOptions
from ..types import RowType
from ..utils import partition_path
from .commit import FileStoreCommit
from .compact import MergeTreeCompactManager, MergeTreeCompactRewriter, UniversalCompaction
from .datafile import DataFileMeta, KeyValueFileReaderFactory, KeyValueFileWriterFactory
from .expire import SnapshotExpire
from .levels import Levels
from .mergefn import MergeExecutor
from .read import MergeFileSplitRead
from .scan import FileStoreScan
from .schema import SchemaManager, TableSchema
from .snapshot import SnapshotManager
from .writer import MergeTreeWriter

__all__ = ["KeyValueFileStore"]


def _parse_per_level(spec: str | None) -> dict[int, str]:
    """'0:avro,5:parquet' -> {0: 'avro', 5: 'parquet'} (reference
    CoreOptions.fileFormatPerLevel / fileCompressionPerLevel)."""
    if not spec:
        return {}
    out: dict[int, str] = {}
    for part in spec.split(","):
        lvl, _, val = part.strip().partition(":")
        if not val:
            raise ValueError(f"per-level spec needs 'level:value' pairs, got {part!r}")
        out[int(lvl)] = val.strip()
    return out


def _resolve_key_bloom(co: CoreOptions) -> bool:
    from ..format.fileindex import resolve_key_bloom

    return resolve_key_bloom(co.options.get(CoreOptions.FILE_INDEX_BLOOM_KEY_ENABLED))


class KeyValueFileStore:
    def __init__(self, file_io: FileIO, table_path: str, schema: TableSchema, commit_user: str = "anonymous"):
        self.table_path = table_path
        self.schema = schema
        self.commit_user = commit_user
        self.options = schema.core_options()
        # resilience layer: every store-level path (scan / merge read /
        # commit / compact / expire) routes its IO through the retrying
        # wrapper, governed by fs.retry.* / fs.io.timeout; with retries
        # disabled the original FileIO is used unwrapped (zero indirection)
        from ..resilience import wrap_file_io

        self.file_io = wrap_file_io(file_io, self.options)
        self.value_schema: RowType = RowType(schema.fields)
        self.key_names = schema.trimmed_primary_keys
        self.partition_keys = list(schema.partition_keys)
        self.schema_manager = SchemaManager(self.file_io, table_path)
        # byte-budget caches (utils.cache): process-wide, shared by scan /
        # read / commit / compaction / lookup through this store's accessors;
        # None when the table opted out via a 0 budget
        from ..utils.cache import table_caches

        self.manifest_obj_cache, self.data_file_obj_cache = table_caches(self.options)
        self.snapshot_manager = SnapshotManager(self.file_io, table_path, cache=self.manifest_obj_cache)
        self._schemas_cache: dict[int, RowType] = {}

    # ---- layout --------------------------------------------------------
    def bucket_dir(self, partition: tuple, bucket: int) -> str:
        pp = partition_path(
            self.partition_keys,
            partition,
            default_name=self.options.options.get(CoreOptions.PARTITION_DEFAULT_NAME),
        )
        base = f"{self.table_path}/{pp}" if pp else self.table_path
        return f"{base}/bucket-{bucket}"

    def schemas_by_id(self) -> dict[int, RowType]:
        for sid, ts in self.schema_manager.all_schemas().items():
            if sid not in self._schemas_cache:
                self._schemas_cache[sid] = RowType(ts.fields)
        if self.schema.id not in self._schemas_cache:
            self._schemas_cache[self.schema.id] = self.value_schema
        return self._schemas_cache

    # ---- components ----------------------------------------------------
    def merge_executor(self) -> MergeExecutor:
        return MergeExecutor(self.value_schema, self.key_names, self.options.merge_engine, self.options)

    keyed = True

    def writer_factory(self, partition: tuple, bucket: int) -> KeyValueFileWriterFactory:
        co = self.options
        bloom_cols = co.options.get(CoreOptions.FILE_INDEX_BLOOM_COLUMNS)
        format_options = {
            k: v
            for k, v in co.options._data.items()
            if k.startswith(("format.", "orc.", "parquet.", "avro."))
        }
        # generic writer knobs the format backends understand
        block = co.options.get(CoreOptions.FILE_BLOCK_SIZE)
        if block is not None:
            format_options.setdefault("file.block-size", int(block))
        format_options.setdefault(
            "file.compression.zstd-level", co.options.get(CoreOptions.FILE_COMPRESSION_ZSTD_LEVEL)
        )
        # encoder selection (format.parquet.encoder = arrow | native); this
        # one seam routes memtable flush, compaction rewrite, changelog and
        # sort-compact writes through the chosen encode backend
        format_options.setdefault(
            "format.parquet.encoder", co.options.get(CoreOptions.FORMAT_PARQUET_ENCODER)
        )
        return KeyValueFileWriterFactory(
            self.file_io,
            self.bucket_dir(partition, bucket),
            self.value_schema,
            self.key_names,
            self.schema.id,
            file_format=co.file_format,
            compression=co.file_compression,
            target_file_size=co.target_file_size,
            bloom_columns=[c.strip() for c in bloom_cols.split(",")] if bloom_cols else (),
            bloom_fpp=co.options.get(CoreOptions.FILE_INDEX_BLOOM_FPP),
            key_bloom=_resolve_key_bloom(co),
            key_bloom_fpp=co.options.get(CoreOptions.FILE_INDEX_BLOOM_KEY_FPP),
            index_in_manifest_threshold=int(
                co.options.get(CoreOptions.FILE_INDEX_IN_MANIFEST_THRESHOLD)
            ),
            keyed=self.keyed,
            format_options=format_options,
            include_key_columns=co.options.get(CoreOptions.DATA_FILE_INCLUDE_KEY_COLUMNS),
            per_level_format=_parse_per_level(co.options.get(CoreOptions.FILE_FORMAT_PER_LEVEL)),
            per_level_compression=_parse_per_level(co.options.get(CoreOptions.FILE_COMPRESSION_PER_LEVEL)),
            parallelism=co.options.get(CoreOptions.SCAN_PARALLELISM),
        )

    def reader_factory(self, partition: tuple, bucket: int, read_schema: RowType | None = None) -> KeyValueFileReaderFactory:
        co = self.options
        # reader-side format options: raw format-scoped keys plus the
        # decoder selection (format.parquet.decoder = arrow | native); this
        # one seam routes core/read, compaction rewrites, sort_compact,
        # lookup and table reads through the chosen decode backend
        format_options = {
            k: v
            for k, v in co.options._data.items()
            if k.startswith(("format.", "orc.", "parquet.", "avro."))
        }
        format_options.setdefault(
            "format.parquet.decoder", co.options.get(CoreOptions.FORMAT_PARQUET_DECODER)
        )
        # compressed-domain merge (merge.dict-domain): readers return
        # dictionary codes for dict-encoded string chunks instead of
        # expanding them — one seam for merge read, compaction, sort-compact
        format_options.setdefault("merge.dict-domain", co.dict_domain)
        format_options.setdefault("merge.dict-domain.pool-limit", co.dict_domain_pool_limit)
        return KeyValueFileReaderFactory(
            self.file_io,
            self.bucket_dir(partition, bucket),
            read_schema or self.value_schema,
            self.schemas_by_id(),
            file_format=co.file_format,
            keyed=self.keyed,
            cache=self.data_file_obj_cache,
            format_options=format_options,
        )

    def pipeline_config(self) -> tuple[int, int | None]:
        """(scan.prefetch-splits, scan.parallelism) — the pipelined split
        scheduler's knobs (parallel/pipeline.py), resolved once here so
        read/compact/flush consumers all agree."""
        from ..parallel.pipeline import pipeline_config

        return pipeline_config(self.options)

    def new_scan(self) -> FileStoreScan:
        manifest_par = self.options.options.get(CoreOptions.SCAN_MANIFEST_PARALLELISM)
        if manifest_par is None:
            # scan.parallelism is the general pipeline knob; the manifest-
            # specific option stays the override
            manifest_par = self.options.options.get(CoreOptions.SCAN_PARALLELISM)
        return FileStoreScan(
            self.file_io,
            self.table_path,
            self.key_names,
            manifest_parallelism=manifest_par,
            cache=self.manifest_obj_cache,
        )

    def new_commit(self) -> FileStoreCommit:
        return FileStoreCommit(
            self.file_io,
            self.table_path,
            self.commit_user,
            self.schema.id,
            self.options,
            cache=self.manifest_obj_cache,
        )

    def new_expire(self, protected_ids=None) -> SnapshotExpire:
        return SnapshotExpire(
            self.file_io, self.table_path, self.options, protected_ids, partition_keys=self.partition_keys
        )

    # ---- write ---------------------------------------------------------
    def restore_files(self, partition: tuple, bucket: int) -> list[DataFileMeta]:
        plan = self.new_scan().with_bucket(bucket).with_partition_filter(lambda p: p == partition).plan()
        return [e.file for e in plan.entries]

    def restore_state(self, partition: tuple, bucket: int):
        """(files, deletion_vectors) for one bucket from the latest snapshot."""
        plan = self.new_scan().with_bucket(bucket).with_partition_filter(lambda p: p == partition).plan()
        files = [e.file for e in plan.entries]
        dvs: dict = {}
        dv_index = plan.dv_index_for(partition, bucket)
        if dv_index:
            from .deletionvectors import DeletionVectorsIndexFile

            dvs = DeletionVectorsIndexFile(self.file_io, self.table_path).read_all(dv_index)
        return files, dvs

    def new_writer(
        self,
        partition: tuple,
        bucket: int,
        total_buckets: int | None = None,
        restore: bool = True,
        admission=None,
    ) -> MergeTreeWriter:
        from ..options import ChangelogProducer

        if self.options.write_only and self.options.changelog_producer == ChangelogProducer.LOOKUP:
            raise ValueError(
                "changelog-producer=lookup needs the writer's levels view and cannot run with "
                "write-only=true (produce the changelog in the writing job, not a dedicated compactor)"
            )
        existing, dvs = self.restore_state(partition, bucket) if restore else ([], {})
        max_seq = max((f.max_sequence_number for f in existing), default=-1)
        levels = Levels(existing, self.options.num_levels)
        merge = self.merge_executor()
        wf = self.writer_factory(partition, bucket)
        compact_manager = None
        if not self.options.write_only:
            strategy = UniversalCompaction(
                self.options.max_size_amplification_percent,
                self.options.size_ratio,
                self.options.num_sorted_runs_compaction_trigger,
                self.options.options.get(CoreOptions.COMPACTION_OPTIMIZATION_INTERVAL),
                max_file_num=self.options.options.get(CoreOptions.COMPACTION_MAX_FILE_NUM),
            )
            from ..options import ChangelogProducer

            rewriter = MergeTreeCompactRewriter(
                self.reader_factory(partition, bucket),
                wf,
                merge,
                deletion_vectors=dvs,
                emit_full_changelog=(
                    self.options.changelog_producer == ChangelogProducer.FULL_COMPACTION
                    or (
                        # lookup producer with lookup-wait=false: changelog
                        # production deferred to compaction (writer skips it)
                        self.options.changelog_producer == ChangelogProducer.LOOKUP
                        and not self.options.options.get(
                            CoreOptions.CHANGELOG_PRODUCER_LOOKUP_WAIT
                        )
                    )
                ),
                row_deduplicate=self.options.options.get(CoreOptions.CHANGELOG_PRODUCER_ROW_DEDUPLICATE),
                expire_predicate=self.record_expire_predicate(),
            )
            compact_manager = MergeTreeCompactManager(levels, strategy, rewriter, self.options)
        debt_gate = None
        if self.options.write_only and self.options.options.get(
            CoreOptions.COMPACTION_ADAPTIVE_INGEST_GATE
        ):
            # write-only ingest has no inline compaction manager bounding its
            # sorted runs: resolve the adaptive scheduler's debt-admission
            # gate lazily per flush, so a service started AFTER this writer
            # still bounds it (ISSUE 12, PR 11 follow-up)
            import functools

            from ..table.compactor import active_debt_gate

            debt_gate = functools.partial(active_debt_gate, self.table_path)
        return MergeTreeWriter(
            partition,
            bucket,
            total_buckets if total_buckets is not None else max(self.options.bucket, 1),
            wf,
            merge,
            compact_manager,
            self.options,
            restored_max_seq=max_seq,
            admission=admission,
            debt_gate=debt_gate,
        )

    # ---- read ----------------------------------------------------------
    def record_expire_predicate(self):
        """Row TTL (reference io/RecordLevelExpire): rows whose time field is
        older than record-level.expire-time.ms are dropped on read and during
        compaction rewrites. The column unit comes from
        record-level.time-field-type (seconds | millis | micros)."""
        ttl = self.options.options.get(CoreOptions.RECORD_LEVEL_EXPIRE_TIME_MS)
        field = self.options.options.get(CoreOptions.RECORD_LEVEL_TIME_FIELD)
        if ttl is None or field is None:
            return None
        from ..data.predicate import greater_than, is_null, or_
        from ..utils import now_millis

        unit = self.options.options.get(CoreOptions.RECORD_LEVEL_TIME_FIELD_TYPE)
        cutoff_ms = now_millis() - ttl
        scale = {"seconds": 1000, "millis": 1, "micros": None}.get(unit, 1000)
        cutoff = cutoff_ms * 1000 if scale is None else cutoff_ms // scale
        # rows with a NULL time field are KEPT, never silently expired: the
        # reference's contract is that the field must be non-null
        # (RecordLevelExpire.java:86-87 checkArgument) — eval would collapse
        # NULL > cutoff to False and permanently drop the row otherwise
        return or_(greater_than(field, cutoff), is_null(field))

    def read_bucket(
        self,
        partition: tuple,
        bucket: int,
        files: list[DataFileMeta],
        predicate=None,
        projection: Sequence[str] | None = None,
        drop_delete: bool = True,
        deletion_vectors: dict | None = None,
    ):
        return self.read_bucket_dispatch(
            partition, bucket, files, predicate, projection, drop_delete, deletion_vectors
        )()

    def read_bucket_dispatch(
        self,
        partition: tuple,
        bucket: int,
        files: list[DataFileMeta],
        predicate=None,
        projection: Sequence[str] | None = None,
        drop_delete: bool = True,
        deletion_vectors: dict | None = None,
    ):
        """Two-phase read_bucket for mesh execution: returns a continuation;
        the merge jobs of all buckets dispatched in one round of the mesh
        executor run in a single batched shard_map."""
        expire = self.record_expire_predicate()
        if expire is not None:
            from ..data.predicate import and_

            predicate = expire if predicate is None else and_(predicate, expire)
        read = MergeFileSplitRead(
            self.reader_factory(partition, bucket),
            self.merge_executor(),
            self.key_names,
            parallelism=self.options.options.get(CoreOptions.SCAN_PARALLELISM),
        )
        return read.read_split_dispatch(files, predicate, projection, drop_delete, deletion_vectors)


class AppendOnlyFileStore(KeyValueFileStore):
    """No-PK store: plain rows, concat reads, small-file compaction
    (reference AppendOnlyFileStore.java:44)."""

    keyed = False

    def new_writer(
        self,
        partition: tuple,
        bucket: int,
        total_buckets: int | None = None,
        restore: bool = True,
        admission=None,  # accepted for signature parity; the append writer
        # buffers through its own spillable path and takes no byte admission
    ):
        from .append import AppendOnlyCompactManager, AppendOnlyWriter

        existing = self.restore_files(partition, bucket) if restore else []
        max_seq = max((f.max_sequence_number for f in existing), default=-1)
        wf = self.writer_factory(partition, bucket)
        compact_manager = None
        if not self.options.write_only:
            compact_manager = AppendOnlyCompactManager(self.reader_factory(partition, bucket), wf, self.options)
        return AppendOnlyWriter(
            partition,
            bucket,
            total_buckets if total_buckets is not None else max(self.options.bucket, 1),
            wf,
            compact_manager,
            self.options,
            existing_files=existing,
            restored_max_seq=max_seq,
        )

    def read_bucket(
        self,
        partition: tuple,
        bucket: int,
        files: list[DataFileMeta],
        predicate=None,
        projection: Sequence[str] | None = None,
        drop_delete: bool = True,
        deletion_vectors: dict | None = None,
    ):
        from ..data.batch import ColumnBatch, concat_batches

        dvs = deletion_vectors or {}
        rf = self.reader_factory(partition, bucket)
        ordered = sorted(files, key=lambda f: (f.min_sequence_number, f.file_name))
        out = []
        for f in ordered:
            dv = dvs.get(f.file_name)
            kv = rf.read(f, predicate=None if dv is not None else predicate)
            if dv is not None:
                mask = ~dv.deleted_mask(kv.num_rows)
                if not mask.all():
                    kv = kv.filter(mask)
            data = kv.data
            if predicate is not None and data.num_rows:
                mask = predicate.eval(data)
                if not mask.all():
                    data = data.filter(mask)
            if projection is not None:
                data = data.select(projection)
            out.append(data)
        if not out:
            schema = self.value_schema if projection is None else self.value_schema.project(projection)
            return ColumnBatch.empty(schema)
        return concat_batches(out)

    def read_bucket_dispatch(self, *args, **kwargs):
        """Append reads have no merge to batch: the continuation just wraps
        the eager concat read (and writes nothing into a caller's `sink`)."""
        out = self.read_bucket(*args, **kwargs)
        return lambda sink=None: out
