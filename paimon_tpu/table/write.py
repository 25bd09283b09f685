"""Write builders: batch and streaming ingestion.

Parity: /root/reference/paimon-core/.../table/sink/ —
BatchWriteBuilderImpl / StreamWriteBuilderImpl, TableWriteImpl.java:48 (row ->
SinkRecord with partition + bucket :129-160), TableCommitImpl.java:72
(filterAndCommit :183 for replay-safe streaming, expire hook :77-127).

A TableWrite routes incoming batches to per-(partition, bucket) merge-tree
writers; prepare_commit() drains them into CommitMessages; TableCommit turns
messages + a commit identifier into snapshots.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Sequence

import numpy as np

from ..core.commit import BATCH_COMMIT_IDENTIFIER
from ..core.manifest import CommitMessage, ManifestCommittable
from ..data.batch import ColumnBatch
from ..metrics import span, write_metrics
from ..types import RowKind

if TYPE_CHECKING:
    from . import FileStoreTable

__all__ = ["BatchWriteBuilder", "StreamWriteBuilder", "TableWrite", "TableCommit"]


class TableWrite:
    def __init__(self, table: "FileStoreTable", buffer_controller=None):
        self.table = table
        store = table.store
        # admission control / memtable backpressure (core/admission.py):
        # built from write.buffer.max-memory when set, or injected — the
        # soak harness shares ONE controller across all writer threads to
        # model a global host-memory budget
        if buffer_controller is None:
            from ..core.admission import WriteBufferController

            buffer_controller = WriteBufferController.from_options(store.options)
        self.admission = buffer_controller
        self.partition_keys = store.partition_keys
        self.bucket_keys = table.schema.bucket_keys
        self.dynamic = table.is_primary_key_table and store.options.bucket == -1
        self.num_buckets = max(store.options.bucket, 1)
        self._writers: dict[tuple, object] = {}
        # the operation id (metrics.span) of the checkpoint being written:
        # allotted at its first write, taken up by prepare_commit, then 0
        self._op = 0
        self._assigner = None
        self._cross = None
        if (
            self.dynamic
            and table.partition_keys
            and not set(table.partition_keys) <= set(table.primary_keys)
        ):
            # primary key omits the partition key: the standard dynamic path
            # cannot keep keys unique across partitions — delegate to the
            # global-index writer (reference GlobalDynamicBucketSink)
            from .crosspartition import CrossPartitionUpsertWrite

            self._init_local_merge()  # validate the option combo even here
            if self._local_merge_cap:
                raise ValueError(
                    "local-merge-buffer-size is not supported with cross-partition upsert"
                )
            self._cross = CrossPartitionUpsertWrite(table)
            return
        if self.dynamic:
            from ..core.bucket_index import HashIndexFile, SimpleHashBucketAssigner
            from ..options import CoreOptions

            target = store.options.options.get(CoreOptions.DYNAMIC_BUCKET_TARGET_ROW_NUM)
            # store.file_io, not table.file_io: the hash index rides the same
            # fs.retry budget as every other store-level IO path
            self._assigner = SimpleHashBucketAssigner(
                HashIndexFile(store.file_io, table.path),
                target,
                initial_buckets=store.options.options.get(CoreOptions.DYNAMIC_BUCKET_INITIAL_BUCKETS),
                num_assigners=store.options.options.get(CoreOptions.DYNAMIC_BUCKET_ASSIGNER_PARALLELISM) or 1,
            )
            self._bootstrapped: set[tuple] = set()
        self._init_local_merge()

    def _init_local_merge(self) -> None:
        """Local pre-merge (reference LocalMergeOperator / FlinkSinkBuilder's
        optional pre-shuffle merge): high-churn keys collapse in a small
        buffer BEFORE bucket routing, shrinking shuffle + memtable traffic.
        Deduplicate engine only — other engines need every record."""
        from ..options import CoreOptions, MergeEngine

        store = self.table.store
        size = int(store.options.options.get(CoreOptions.LOCAL_MERGE_BUFFER_SIZE))
        self._local_merge_bytes = 0
        self._local_buffer: list[tuple[ColumnBatch, np.ndarray | None]] = []
        self._local_merge_cap = 0
        if size > 0:
            if store.options.merge_engine != MergeEngine.DEDUPLICATE:
                raise ValueError("local-merge-buffer-size requires merge-engine=deduplicate")
            if not self.table.is_primary_key_table:
                raise ValueError("local-merge-buffer-size requires a primary-key table")
            if store.options.sequence_field:
                # the buffer dedups by ARRIVAL order; a user sequence field
                # could make a lower-seq late arrival evict a higher-seq row
                raise ValueError("local-merge-buffer-size cannot combine with sequence.field")
            if store.options.ignore_delete:
                # a trailing -D would evict its insert here, then be dropped
                # downstream — losing the row ignore-delete meant to keep
                raise ValueError("local-merge-buffer-size cannot combine with ignore-delete")
            self._local_merge_cap = size

    def _local_merge_flush(self) -> None:
        if not self._local_buffer:
            return
        from ..data.batch import concat_batches
        from ..data.keys import encode_key_lanes_with_pools
        from ..ops.merge import deduplicate_select

        batches = [b for b, _ in self._local_buffer]
        kinds = [
            k if k is not None else np.full(b.num_rows, int(RowKind.INSERT), dtype=np.uint8)
            for b, k in self._local_buffer
        ]
        self._local_buffer = []
        self._local_merge_bytes = 0
        data = concat_batches(batches) if len(batches) > 1 else batches[0]
        kind = np.concatenate(kinds)
        # the FULL primary key (partition columns included): the buffer spans
        # partitions, and trimmed keys would collapse same-id rows of
        # DIFFERENT partitions into one — routing separates them downstream
        keys = list(self.table.primary_keys)
        lanes = encode_key_lanes_with_pools(data, keys)
        # stability = arrival order: the LAST record per key (with its kind)
        # survives, exactly what dedup would do downstream
        take = deduplicate_select(lanes)
        self._route(data.take(take), kind.take(take))

    def write(self, data: ColumnBatch | dict, kinds: np.ndarray | Sequence[str] | None = None) -> None:
        with span("write", new_op=not self._op, op=self._op) as sp:
            self._op = sp.op
            if isinstance(data, dict):
                data = ColumnBatch.from_pydict(self.table.row_type, data)
            sp.add(rows=data.num_rows)
            write_metrics().counter("rows").inc(data.num_rows)
            self._write(data, kinds)

    def _write(self, data: ColumnBatch, kinds: np.ndarray | Sequence[str] | None) -> None:
        if kinds is not None and not isinstance(kinds, np.ndarray):
            kinds = np.array([int(RowKind.from_short_string(k)) for k in kinds], dtype=np.uint8)
        if kinds is None:
            # rowkind.field: the row kind rides in a data column ('+I'...)
            from ..options import CoreOptions

            rk_field = self.table.options.options.get(CoreOptions.ROWKIND_FIELD)
            if rk_field:
                vals = data.column(rk_field).values
                kinds = np.array([int(RowKind.from_short_string(str(v))) for v in vals], dtype=np.uint8)
        if self._cross is not None:
            self._cross.write(data, kinds)
            return
        if self._local_merge_cap:
            self._local_buffer.append((data, kinds))
            self._local_merge_bytes += data.byte_size()
            if self._local_merge_bytes >= self._local_merge_cap:
                self._local_merge_flush()
            return
        self._route(data, kinds)

    def _route(self, data: ColumnBatch, kinds: np.ndarray | None) -> None:
        from .bucket import group_by_partition_bucket

        if self.dynamic:
            self._write_dynamic(data, kinds)
            return
        buckets = 0
        for partition, bucket, rows in group_by_partition_bucket(
            data, self.partition_keys, self.bucket_keys, self.num_buckets
        ):
            w = self._writer(partition, bucket)
            sub = data.take(rows) if len(rows) != data.num_rows else data
            sub_kinds = kinds.take(rows) if kinds is not None and len(rows) != data.num_rows else kinds
            w.write(sub, sub_kinds)
            buckets += 1
        sp = span.current()
        if sp is not None:
            sp.add(buckets=buckets)

    def _write_dynamic(self, data: ColumnBatch, kinds) -> None:
        """Dynamic bucket: assign each key a durable bucket via the hash
        index (reference DynamicBucketSink: assigner stage before writers)."""
        from .bucket import group_by_partition_bucket, key_hashes

        store = self.table.store
        for partition, _, rows in group_by_partition_bucket(data, self.partition_keys, [], 1):
            sub = data.take(rows) if len(rows) != data.num_rows else data
            sub_kinds = kinds.take(rows) if kinds is not None and len(rows) != data.num_rows else kinds
            self._bootstrap_partition(partition)
            hashes = key_hashes(sub, store.key_names)
            buckets = self._assigner.assign(partition, hashes)
            for b in np.unique(buckets):
                mask = buckets == b
                w = self._writer(partition, int(b))
                w.write(sub.filter(mask), sub_kinds[mask] if sub_kinds is not None else None)

    def _bootstrap_partition(self, partition: tuple) -> None:
        if partition in self._bootstrapped:
            return
        self._bootstrapped.add(partition)
        from ..core.bucket_index import HashIndexFile

        plan = self.table.store.new_scan().with_partition_filter(lambda p: p == partition).plan()
        hif = HashIndexFile(self.table.store.file_io, self.table.path)
        indexes = {
            e.bucket: hif.read(e.file_name)
            for e in plan.index_entries
            if e.kind == "HASH_INDEX" and e.partition == partition
        }
        if indexes:
            self._assigner.bootstrap(partition, indexes)

    def _writer(self, partition: tuple, bucket: int):
        key = (partition, bucket)
        if key not in self._writers:
            total = -1 if self.dynamic else self.num_buckets
            self._writers[key] = self.table.store.new_writer(
                partition, bucket, total, admission=self.admission
            )
        return self._writers[key]

    def delta_snapshot(self) -> dict[tuple, tuple]:
        """{(partition, bucket): (buffered KVBatches, uncommitted level-0
        DataFileMetas)} across every merge-tree writer this write opened —
        the read-your-writes delta tier LocalTableQuery.attach_write serves
        (committed-plus-buffered gets)."""
        out: dict[tuple, tuple] = {}
        for pb, w in list(self._writers.items()):
            ds = getattr(w, "delta_snapshot", None)
            if ds is not None:
                out[pb] = ds()
        return out

    def compact(self, full: bool = False) -> None:
        """Compact every bucket this write touched — or, when no rows were
        written (dedicated compact job), every live bucket of the table.
        Under merge.engine = mesh the per-bucket flushes and rewrite merges
        batch into shard_map calls over the mesh (the TPU analog of the
        reference's one-compaction-task-per-bucket topology)."""
        if not self._writers:
            plan = self.table.store.new_scan().plan()
            for partition, buckets in plan.grouped().items():
                for bucket in buckets:
                    self._writer(partition, bucket)
        from ..parallel.mesh_exec import maybe_mesh_exec

        with maybe_mesh_exec(self.table.store.options) as mex:
            if mex is None:
                for w in self._writers.values():
                    w.compact(full=full)
                return
            self._batched_flush()
            writers = list(self._writers.values())
            if len(writers) > 1:
                # bucket dispatches (input reads + merge enqueue) stream
                # through the feeder, one lane per device, so bucket i+1's IO
                # overlaps while bucket i's merges batch
                from ..parallel.pipeline import SplitPipeline

                lanes = mex.feeder_lanes
                pipe = SplitPipeline(parallelism=lanes, depth=lanes, stage="compact")

                def dispatch(w):
                    with mex.active():
                        return w.compact_dispatch(full)

                states = list(zip(writers, pipe.map_ordered(writers, dispatch)))
            else:
                states = [(w, w.compact_dispatch(full)) for w in writers]
            for w, st in states:
                w.compact_complete(st)

    def _batched_flush(self) -> None:
        """Dispatch every writer's memtable flush, then complete: the merges
        run in one batched mesh call (reference: one writer task per bucket)."""
        states = [(w, w.flush_dispatch()) for w in self._writers.values()]
        for w, st in states:
            if st is not None:
                w.flush_complete(st)

    def prepare_commit(self) -> list[CommitMessage]:
        op, self._op = self._op, 0
        write_metrics().counter("commits").inc()
        with span("prepare_commit", new_op=not op, op=op):
            return self._prepare_commit()

    def _prepare_commit(self) -> list[CommitMessage]:
        if self._cross is not None:
            return self._cross.prepare_commit()
        if self._local_merge_cap:
            self._local_merge_flush()
        from ..options import CoreOptions

        if self.table.options.options.get(CoreOptions.COMMIT_FORCE_COMPACT) and not self.table.options.write_only:
            self.compact(full=True)
        from ..parallel.mesh_exec import maybe_mesh_exec

        with maybe_mesh_exec(self.table.store.options) as mex:
            if mex is not None:
                self._batched_flush()
            msgs = [m for m in (w.prepare_commit() for w in self._writers.values()) if not m.is_empty()]
        if self._assigner is not None:
            by_pb = {(m.partition, m.bucket): m for m in msgs}
            for partition, entries in self._assigner.prepare_commit().items():
                for e in entries:
                    msg = by_pb.get((partition, e.bucket))
                    if msg is None:
                        msg = CommitMessage(partition, e.bucket, -1)
                        msgs.append(msg)
                        by_pb[(partition, e.bucket)] = msg
                    msg.new_index_files.append(e)
        return msgs

    def close(self) -> None:
        """Tear down every per-bucket writer. Each close releases that
        writer's outstanding buffer reservation back to the (possibly
        shared) admission controller — abandoning a conflicted commit must
        re-admit blocked rivals, never leak budget."""
        for w in self._writers.values():
            close = getattr(w, "close", None)
            if close is not None:
                close()
        self._writers.clear()

    def health(self) -> dict:
        """Writer-side flow-control snapshot: the admission controller's
        backpressure state plus per-bucket buffer/flush depths (the health
        surface a serving layer polls to decide shedding vs routing)."""
        writers = {}
        for (partition, bucket), w in self._writers.items():
            h = getattr(w, "health", None)
            if h is not None:
                writers[f"{partition}/{bucket}"] = h()
        out = {"state": "ok", "writers": writers}
        if self.admission is not None:
            out.update(self.admission.health_dict())
        out["buffered_rows"] = sum(w.get("buffered_rows", 0) for w in writers.values())
        out["pending_flushes_writers"] = sum(w.get("pending_flushes", 0) for w in writers.values())
        return out


def load_callbacks(table, option) -> list:
    """Resolve a 'module:function,module:function' option into callables
    (reference commit.callbacks/tag.callbacks load classes by name; here the
    python-native form). Unresolvable specs raise at load time — a silently
    dropped callback is worse than a loud config error."""
    spec = table.options.options.get(option)
    if not spec:
        return []
    import importlib

    out = []
    for item in spec.split(","):
        mod, _, fn = item.strip().partition(":")
        out.append(getattr(importlib.import_module(mod), fn))
    return out


class TableCommit:
    def __init__(self, table: "FileStoreTable", expire_after_commit: bool = True):
        self.table = table
        self._commit = table.store.new_commit()
        self.expire_after_commit = expire_after_commit

    def commit_messages(self, identifier: int, messages: list[CommitMessage], watermark: int | None = None) -> list[int]:
        c = ManifestCommittable(identifier, watermark=watermark, messages=messages)
        if identifier != BatchWriteBuilder.COMMIT_IDENTIFIER:
            # streaming identifiers are monotonic per user: route through the
            # replay filter so a crash-retry with a rebuilt committable (same
            # identifier) cannot double-apply a phase that already landed
            remaining = self._commit.filter_committed([c])
            if not remaining:
                return []
            c = remaining[0]
        snapshot_ids = self._commit.commit(c)
        self._post_commit()
        return snapshot_ids

    def filter_and_commit(self, committables: list[ManifestCommittable]) -> int:
        """Replay-safe streaming commit (reference filterAndCommit): already-
        committed identifiers are skipped; returns #committed."""
        remaining = self._commit.filter_committed(committables)
        for c in sorted(remaining, key=lambda x: x.commit_identifier):
            self._commit.commit(c)
        if remaining:
            self._post_commit()
        return len(remaining)

    def overwrite(self, identifier: int, messages: list[CommitMessage], partition_filter=None) -> list[int]:
        c = ManifestCommittable(identifier, messages=messages)
        ids = self._commit.overwrite(c, partition_filter)
        self._post_commit()
        return ids

    def _post_commit(self) -> None:
        from ..options import CoreOptions

        snap = self.table.store.snapshot_manager.latest_snapshot()
        for fn in load_callbacks(self.table, CoreOptions.COMMIT_CALLBACKS):
            try:
                fn(self.table, snap)
            except Exception:
                pass  # callbacks must never fail a commit
        try:
            from .tags import TagAutoCreation

            TagAutoCreation(self.table).run()
        except Exception:
            pass  # tagging is maintenance
        if self.expire_after_commit:
            try:
                self.table.expire_snapshots()
            except Exception:
                pass  # expiry is maintenance, never fails a commit
            self._maybe_expire_partitions()

    def _maybe_expire_partitions(self) -> None:
        """Piggyback partition TTL sweeps on commits, rate-limited by
        partition.expiration-check-interval (reference PartitionExpire is
        wired into the committer the same way)."""
        from ..options import CoreOptions
        from ..utils import now_millis

        opts = self.table.options.options
        ttl = opts.get(CoreOptions.PARTITION_EXPIRATION_TIME_MS)
        if ttl is None or not self.table.partition_keys:
            return
        interval = opts.get(CoreOptions.PARTITION_EXPIRATION_CHECK_INTERVAL)
        now = now_millis()
        # rate-limit state lives on the STORE (one per table instance):
        # TableCommit objects are per-commit, so instance state here would
        # make the interval inert and put a full scan on every commit
        store = self.table.store
        last = getattr(store, "_last_partition_expire_check", 0)
        if now - last < (interval or 0):
            return
        store._last_partition_expire_check = now
        try:
            from .maintenance import expire_partitions

            # partition.timestamp-pattern picks the column ('$dt' form);
            # partition.timestamp-formatter is a strptime pattern here
            col_spec = opts.get(CoreOptions.PARTITION_TIMESTAMP_PATTERN)
            expire_partitions(
                self.table,
                ttl,
                time_col=col_spec.lstrip("$") if col_spec else None,
                pattern=opts.get(CoreOptions.PARTITION_TIMESTAMP_FORMATTER) or "%Y-%m-%d",
            )
        except Exception:
            pass  # maintenance must never fail the commit


class BatchWriteBuilder:
    """One-shot batch job: write() everything, then commit() once
    (identifier is fixed — batch jobs have a single commit)."""

    COMMIT_IDENTIFIER = BATCH_COMMIT_IDENTIFIER  # reference uses Long.MAX_VALUE

    def __init__(self, table: "FileStoreTable"):
        self.table = table
        self._overwrite = False
        self._partition_filter = None

    def with_overwrite(self, partition_filter=None) -> "BatchWriteBuilder":
        self._overwrite = True
        self._partition_filter = partition_filter
        return self

    def new_write(self) -> TableWrite:
        return TableWrite(self.table)

    def new_commit(self) -> "BatchTableCommit":
        return BatchTableCommit(self.table, self._overwrite, self._partition_filter)


class BatchTableCommit(TableCommit):
    def __init__(self, table: "FileStoreTable", overwrite: bool, partition_filter):
        super().__init__(table)
        self._overwrite = overwrite
        self._partition_filter = partition_filter

    def commit(self, messages: list[CommitMessage]) -> list[int]:
        from ..options import CoreOptions

        opts = self.table.options.options
        ident = BatchWriteBuilder.COMMIT_IDENTIFIER
        if self._overwrite:
            pf = self._partition_filter
            if pf is None and self.table.partition_keys and opts.get(CoreOptions.DYNAMIC_PARTITION_OVERWRITE):
                # dynamic mode (reference default): only the partitions the
                # new data touches are replaced, not the whole table
                touched = {m.partition for m in messages}
                pf = lambda p: p in touched  # noqa: E731
            return self.overwrite(ident, messages, pf)
        if not messages and not opts.get(CoreOptions.COMMIT_FORCE_CREATE_SNAPSHOT):
            return []  # reference batch commits ignore empty by default
        return self.commit_messages(ident, messages)


class StreamWriteBuilder:
    """Continuous ingestion: per-checkpoint identifiers, replay-safe commits."""

    def __init__(self, table: "FileStoreTable"):
        self.table = table

    def new_write(self) -> TableWrite:
        return TableWrite(self.table)

    def new_commit(self) -> TableCommit:
        return TableCommit(self.table)
