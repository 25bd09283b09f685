"""Read builders: scan planning -> splits -> merge reads.

Parity: /root/reference/paimon-core/.../table/source/ —
ReadBuilder.java:73 (scan -> plan -> splits -> read), DataSplit.java:48,
MergeTreeSplitGenerator.java:38 (section-aware split packing reusing
IntervalPartition), DataTableBatchScan with time travel via scan options
(CoreOptions.StartupMode).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Sequence

from ..core.datafile import DataFileMeta
from ..core.levels import IntervalPartition
from ..data.predicate import Predicate
from ..options import CoreOptions

if TYPE_CHECKING:
    from . import FileStoreTable

__all__ = ["ReadBuilder", "TableScan", "TableRead", "DataSplit"]


@dataclass
class DataSplit:
    """A self-contained unit of read work (serializable for shipping to
    tasks/devices)."""

    partition: tuple
    bucket: int
    files: list[DataFileMeta]
    snapshot_id: int | None = None
    raw_convertible: bool = False  # single-run: no merge needed
    dv_index_file: str | None = None  # deletion-vector index for this bucket
    is_changelog: bool = False  # files are changelog (-U/+U kinds preserved)

    @property
    def row_count(self) -> int:
        return sum(f.row_count for f in self.files)

    def to_dict(self) -> dict:
        return {
            "partition": list(self.partition),
            "bucket": self.bucket,
            "files": [f.to_dict() for f in self.files],
            "snapshotId": self.snapshot_id,
            "rawConvertible": self.raw_convertible,
            "dvIndexFile": self.dv_index_file,
            "isChangelog": self.is_changelog,
        }

    @staticmethod
    def from_dict(d: dict) -> "DataSplit":
        return DataSplit(
            tuple(d["partition"]),
            d["bucket"],
            [DataFileMeta.from_dict(f) for f in d["files"]],
            d.get("snapshotId"),
            d.get("rawConvertible", False),
            d.get("dvIndexFile"),
            d.get("isChangelog", False),
        )


class ReadBuilder:
    def __init__(self, table: "FileStoreTable"):
        self.table = table
        self._predicate: Predicate | None = None
        self._projection: Sequence[str] | None = None
        self._limit: int | None = None

    def with_filter(self, predicate: Predicate) -> "ReadBuilder":
        self._predicate = predicate if self._predicate is None else (self._predicate & predicate)
        return self

    def with_projection(self, fields: Sequence[str]) -> "ReadBuilder":
        self._projection = list(fields)
        return self

    def with_limit(self, limit: int) -> "ReadBuilder":
        self._limit = limit
        return self

    def new_scan(self) -> "TableScan":
        return TableScan(self.table, self._predicate)

    def new_stream_scan(self):
        from .stream import StreamTableScan

        return StreamTableScan(self.table, self._predicate)

    def new_read(self) -> "TableRead":
        return TableRead(self.table, self._predicate, self._projection, self._limit)


class TableScan:
    def __init__(self, table: "FileStoreTable", predicate: Predicate | None):
        self.table = table
        self.predicate = predicate

    def _incremental_splits(self, spec: str) -> list[DataSplit]:
        """incremental-between='a,b' (snapshot ids or tag names): the union
        of APPEND deltas of snapshots (a, b], rows carrying their original
        kinds (reference IncrementalStartingScanner, delta scan mode)."""
        store = self.table.store
        sm = store.snapshot_manager

        def resolve(token: str) -> int:
            token = token.strip()
            if token.lstrip("-").isdigit():
                return int(token)
            from .tags import TagManager

            try:
                return TagManager(self.table.file_io, self.table.path).snapshot_id(token)
            except FileNotFoundError:
                raise ValueError(f"unknown tag {token!r} in incremental-between") from None

        parts = spec.split(",")
        if len(parts) != 2:
            raise ValueError(f"incremental-between expects 'start,end', got {spec!r}")
        start, end = resolve(parts[0]), resolve(parts[1])
        if start >= end:
            raise ValueError(
                f"incremental-between start must precede end, got {start} >= {end}"
            )
        from ..core.snapshot import CommitKind

        mode = store.options.options.get(CoreOptions.INCREMENTAL_BETWEEN_SCAN_MODE).lower()
        if mode not in ("delta", "changelog"):
            raise ValueError(f"unknown incremental-between-scan-mode {mode!r}")
        partition_accept = self._partition_predicate()
        splits: list[DataSplit] = []
        for sid in range(start + 1, end + 1):
            if not sm.snapshot_exists(sid):
                continue
            snap = sm.snapshot(sid)
            if mode == "changelog":
                # exact change events the producers recorded (reference
                # scan-mode=changelog); COMPACT snapshots carry the
                # full-compaction producer's files, so none are skipped
                if not snap.changelog_manifest_list:
                    continue
                kind = "changelog"
            else:
                if snap.commit_kind != CommitKind.APPEND:
                    continue  # COMPACT/OVERWRITE rewrite existing rows, no new changes
                kind = "delta"
            scan = store.new_scan().with_snapshot(sid).with_kind(kind)
            if partition_accept is not None:
                scan = scan.with_partition_filter(partition_accept)
            plan = scan.plan()
            for partition, buckets in sorted(plan.grouped().items()):
                for bucket, files in sorted(buckets.items()):
                    splits.append(
                        DataSplit(
                            partition,
                            bucket,
                            files,
                            snapshot_id=sid,
                            # raw per-file reads preserving row kinds: the
                            # delta IS the change stream for this snapshot
                            is_changelog=True,
                        )
                    )
        return splits

    def _file_index_predicate(self, keyed: bool):
        """The predicate to test against per-file bloom indexes, or None when
        index pruning is off/inapplicable. Keyed tables only test KEY-field
        conjuncts (a value match in an old file can be overridden by a newer
        one, but a key absent from every index cannot exist); append tables
        test everything — same safety split as the stats-based filters.
        Gated by file-index.read.enabled (reference FileIndexReadOptions)."""
        if self.predicate is None:
            return None
        co = self.table.store.options
        if not co.options.get(CoreOptions.FILE_INDEX_READ_ENABLED):
            return None
        if not keyed:
            return self.predicate
        from ..data.predicate import PredicateBuilder, and_

        parts = PredicateBuilder.pick_by_fields(
            PredicateBuilder.split_and(self.predicate), set(self.table.store.key_names)
        )
        return and_(*parts) if parts else None

    def _index_accepts(self, f, bucket_dir: str, pred) -> bool:
        """False only when the file's index PROVES no row matches."""
        from ..format.fileindex import FileIndexPredicate

        try:
            if f.embedded_index is not None:
                return FileIndexPredicate.from_bytes(f.embedded_index).test(pred)
            if f"{f.file_name}.index" in f.extra_files:
                return FileIndexPredicate(
                    self.table.file_io, f"{bucket_dir}/{f.file_name}.index"
                ).test(pred)
        except (FileNotFoundError, OSError):
            return True  # a missing/corrupt index never loses rows
        return True

    def _partition_predicate(self):
        """partition tuple -> bool from the scan predicate's partition
        conjuncts; None when nothing prunes."""
        if self.predicate is None:
            return None
        from ..data.predicate import PredicateBuilder, and_

        store = self.table.store
        parts = PredicateBuilder.split_and(self.predicate)
        part_parts = PredicateBuilder.pick_by_fields(parts, set(store.partition_keys))
        if not part_parts:
            return None
        pred = and_(*part_parts)
        keys = store.partition_keys

        def accept(partition: tuple) -> bool:
            from ..data.batch import ColumnBatch

            row = ColumnBatch.from_pydict(
                self.table.row_type.project(keys), {k: [v] for k, v in zip(keys, partition)}
            )
            return bool(pred.eval(row)[0])

        return accept

    def _resolve_snapshot(self) -> int | None:
        """Time travel via scan options (reference StartupMode/time-travel)."""
        store = self.table.store
        opts = store.options.options
        sid = opts.get(CoreOptions.SCAN_SNAPSHOT_ID)
        if sid is not None:
            return sid
        tag = opts.get(CoreOptions.SCAN_TAG_NAME)
        if tag:
            from .tags import TagManager

            return TagManager(self.table.file_io, self.table.path).snapshot_id(tag)
        ts = opts.get(CoreOptions.SCAN_TIMESTAMP_MILLIS)
        if ts is None:
            iso = opts.get(CoreOptions.SCAN_TIMESTAMP)
            if iso:
                import datetime as _dt

                ts = int(_dt.datetime.fromisoformat(iso).timestamp() * 1000)
        if ts is not None:
            snap = store.snapshot_manager.earlier_or_equal_time_millis(ts)
            return snap.id if snap else None
        version = opts.get(CoreOptions.SCAN_VERSION)
        if version:
            from .tags import TagManager

            tm = TagManager(self.table.file_io, self.table.path)
            if version in tm.list_tags():
                return tm.snapshot_id(version)
            return int(version)
        wm = opts.get(CoreOptions.SCAN_WATERMARK)
        if wm is not None:
            # earliest snapshot whose watermark passed the bound (reference
            # TimeTravelUtil watermark travel)
            for snap in store.snapshot_manager.snapshots():
                if snap.watermark is not None and snap.watermark >= wm:
                    return snap.id
            return None
        return None

    def plan(self) -> list[DataSplit]:
        store = self.table.store
        inc = store.options.options.get(CoreOptions.INCREMENTAL_BETWEEN)
        if inc:
            return self._incremental_splits(inc)
        inc_ts = store.options.options.get(CoreOptions.INCREMENTAL_BETWEEN_TIMESTAMP)
        if inc_ts:
            # resolve 't1,t2' epoch-millis to the snapshots at those times,
            # then reuse the id-based incremental machinery
            t1, t2 = (int(x) for x in inc_ts.split(","))
            sm = store.snapshot_manager
            s1 = sm.earlier_or_equal_time_millis(t1)
            s2 = sm.earlier_or_equal_time_millis(t2)
            if s2 is None:
                return []
            start = s1.id if s1 else 0
            if start >= s2.id:
                return []  # empty window: no snapshot landed between t1 and t2
            return self._incremental_splits(f"{start},{s2.id}")
        scan = store.new_scan()
        snapshot_id = self._resolve_snapshot()
        if snapshot_id is not None:
            scan = scan.with_snapshot(snapshot_id)
        if self.predicate is not None:
            from ..data.predicate import PredicateBuilder, and_

            parts = PredicateBuilder.split_and(self.predicate)
            key_parts = PredicateBuilder.pick_by_fields(parts, set(store.key_names))
            if key_parts:
                scan = scan.with_key_filter(and_(*key_parts))
            if not self.table.schema.primary_keys:
                # append tables: every row is final — value filters can
                # safely skip whole files (reference AppendOnlyFileStoreScan)
                scan = scan.with_value_filter(self.predicate)
            # partition predicate -> partition pruning
            accept = self._partition_predicate()
            if accept is not None:
                scan = scan.with_partition_filter(accept)
        plan = scan.plan()
        co = store.options
        target = int(co.options.get(CoreOptions.SOURCE_SPLIT_TARGET_SIZE))
        open_cost = int(co.options.get(CoreOptions.SOURCE_SPLIT_OPEN_FILE_COST))
        created_after = co.options.get(CoreOptions.SCAN_FILE_CREATION_TIME_MILLIS)
        splits = []
        keyed = bool(self.table.schema.primary_keys)
        index_pred = self._file_index_predicate(keyed)
        per_partition: dict[tuple, list[DataSplit]] = {}
        for partition, buckets in sorted(plan.grouped().items(), key=lambda kv: kv[0]):
            plist = per_partition.setdefault(partition, [])
            for bucket, files in sorted(buckets.items()):
                if created_after is not None:
                    # reference scan.file-creation-time-millis: only files
                    # born after the bound (append/log-style consumption)
                    files = [f for f in files if f.creation_time_millis > created_after]
                    if not files:
                        continue
                if index_pred is not None:
                    bd = store.bucket_dir(partition, bucket)
                    files = [f for f in files if self._index_accepts(f, bd, index_pred)]
                    if not files:
                        continue
                snapshot = plan.snapshot.id if plan.snapshot else None
                dv_index = plan.dv_index_for(partition, bucket)
                for pack, raw in _pack_bucket_splits(files, target, open_cost, keyed):
                    plist.append(
                        DataSplit(
                            partition,
                            bucket,
                            pack,
                            snapshot_id=snapshot,
                            raw_convertible=raw,
                            dv_index_file=dv_index,
                        )
                    )
        if co.options.get(CoreOptions.SCAN_PLAN_SORT_PARTITION):
            # strict partition-major order for sorted sequential consumption
            for p in sorted(per_partition):
                splits.extend(per_partition[p])
        else:
            # round-robin across partitions: parallel readers spread load
            lanes = [per_partition[p] for p in sorted(per_partition)]
            i = 0
            while True:
                emitted = False
                for lane in lanes:
                    if i < len(lane):
                        splits.append(lane[i])
                        emitted = True
                if not emitted:
                    break
                i += 1
        return splits


def _pack_bucket_splits(files, target: int, open_cost: int, keyed: bool) -> list[tuple[list, bool]]:
    """Weighted bin-packing of one bucket's files into read splits, returning
    (files, raw_convertible) per pack (reference
    MergeTreeSplitGenerator.splitForBatch + AppendOnlySplitGenerator +
    BinPacking.packForOrdered). Keyed tables pack SECTIONS — files that must
    merge together stay atomic, key-disjoint sections spread across splits —
    weighing each section max(total size, open-file-cost); append tables have
    no key ranges (one degenerate section), so their unit is the single file.
    Not ported: the reference's DV/first-row fast path that packs per-file
    raw groups even for overlapping keyed sections."""
    if not files:
        return []
    if keyed:
        sections = IntervalPartition(files).partition()
        units = [
            ([f for run in section for f in run.files], len(section) == 1)
            for section in sections
        ]
    else:
        ordered = sorted(files, key=lambda f: (f.min_sequence_number, f.file_name))
        units = [([f], True) for f in ordered]
    packs: list[tuple[list, bool]] = []
    cur: list = []
    cur_raw = True
    cur_weight = 0
    for unit_files, unit_raw in units:
        w = max(sum(f.file_size for f in unit_files), open_cost)
        if cur and cur_weight + w > target:
            packs.append((cur, cur_raw))
            cur, cur_raw, cur_weight = [], True, 0
        cur.extend(unit_files)
        cur_raw = cur_raw and unit_raw
        cur_weight += w
    if cur:
        packs.append((cur, cur_raw))
    return packs


class TableRead:
    def __init__(
        self,
        table: "FileStoreTable",
        predicate: Predicate | None,
        projection: Sequence[str] | None,
        limit: int | None = None,
    ):
        self.table = table
        self.predicate = predicate
        self.projection = projection
        self.limit = limit

    def read_with_kinds(self, split: DataSplit):
        """(rows, RowKind uint8 vector) — the changelog-aware read used by
        streaming consumers. For data splits every merged row is +I."""
        import numpy as np

        from ..types import RowKind

        if split.is_changelog:
            store = self.table.store
            rf = store.reader_factory(split.partition, split.bucket)
            from ..core.kv import KVBatch

            ordered = sorted(split.files, key=lambda f: (f.min_sequence_number, f.file_name))
            kv = KVBatch.concat([rf.read(f) for f in ordered])
            data = kv.data
            kinds = kv.kind
            if self.predicate is not None and data.num_rows:
                mask = self.predicate.eval(data)
                if not mask.all():
                    data, kinds = data.filter(mask), kinds[mask]
            if self.projection is not None:
                data = data.select(self.projection)
            return data, kinds
        out = self.read(split)
        return out, np.full(out.num_rows, int(RowKind.INSERT), dtype=np.uint8)

    def read(self, split: DataSplit, sink=None):
        """One split's batch. `sink`: the BatchSink of the read_all that
        appends this batch next (see _dispatch)."""
        if split.is_changelog:
            return self.read_with_kinds(split)[0]
        out = self._dispatch(split)(sink)
        if self.limit is not None and out.num_rows > self.limit:
            out = out.slice(0, self.limit)
        return out

    def _dispatch(self, split: DataSplit):
        """Phase-1 read of one data split: returns a continuation. Called
        with the BatchSink that its batch is appended to next, by the thread
        that calls it, a split that can writes its winners into the sink's
        rows and not into arrays of its own."""
        dvs = None
        if split.dv_index_file:
            from ..core.deletionvectors import DeletionVectorsIndexFile

            all_dvs = DeletionVectorsIndexFile(self.table.file_io, self.table.path).read_all(split.dv_index_file)
            names = {f.file_name for f in split.files}
            dvs = {k: v for k, v in all_dvs.items() if k in names}
        return self.table.store.read_bucket_dispatch(
            split.partition,
            split.bucket,
            split.files,
            predicate=self.predicate,
            projection=self.projection,
            deletion_vectors=dvs,
        )

    def batches(self, splits: Sequence[DataSplit], sink=None):
        """Ordered generator of per-split batches (the ConcatRecordReader
        analog): each split's output is yielded as soon as its merge stage
        completes, in deterministic split order, instead of materializing
        every split before the first row is visible. Three execution modes,
        picked per call:

        * mesh execution (merge.engine = mesh, >1 device, no limit): the
          SplitPipeline becomes the host-side feeder — one prefetch lane per
          device — so IO/decode of round i+1 overlaps the batched shard_map
          merges of round i (_mesh_batches; any number of splits, a single
          split is a round of one job);
        * pipelined (scan.prefetch-splits > 0, the default, several splits,
          no limit): split i+1 fetches bytes through RetryingFileIO and
          decodes on a pipeline worker while split i merges on device —
          output is bit-identical to the sequential path
          (parallel/pipeline.py contract);
        * sequential (scan.prefetch-splits = 0, a single split, or a limit:
          a limit wants early exit split by split — dispatching every split
          up front would turn a point query into a full scan).

        `sink`: the BatchSink of a caller that appends each batch as it is
        yielded (read_all). The mesh and the sequential mode resolve a split
        on this thread in split order, so they hand it its sink (_dispatch); the
        pipelined mode finishes splits out of order on its workers, and a
        limit cuts a batch short: their batches are appended as they are."""
        from ..parallel.mesh_exec import maybe_mesh_exec

        splits = list(splits)
        remaining = self.limit
        if remaining is None:
            with maybe_mesh_exec(self.table.store.options) as mex:
                if mex is not None:
                    yield from self._mesh_batches(mex, splits, sink)
                    return
            if len(splits) > 1:
                depth, parallelism = self.table.store.pipeline_config()
                if depth > 0:
                    from ..parallel.pipeline import SplitPipeline

                    pipe = SplitPipeline(parallelism, depth, stage="scan")
                    yield from pipe.map_ordered(splits, self.read)
                    return
        for s in splits:
            b = self.read(s, sink if remaining is None else None)
            if remaining is not None:
                if remaining <= 0:
                    break
                if b.num_rows > remaining:
                    b = b.slice(0, remaining)
                remaining -= b.num_rows
            yield b

    def _mesh_batches(self, mex, splits: Sequence[DataSplit], sink=None):
        """merge.engine = mesh scan: the PR 4 SplitPipeline is the host-side
        feeder with one prefetch lane per device, so the IO + decode of
        round i+1 overlap the batched device merges of round i. A round is
        the next `feeder_lanes` data splits in split order, fixed here before
        any split dispatches: its merge jobs run in family-batched shard_map
        calls over the mesh's bucket axis once all of its dispatches are in,
        and never with jobs of a later round that the feeder has submitted
        already. So the calls an operation makes, and the rows they pad, are
        the plan's and not a matter of thread timing. Emission stays in strict
        split order, so output is bit-identical to the single-device path."""
        from ..metrics import mesh_metrics, span
        from ..parallel.pipeline import SplitPipeline

        lanes = mex.feeder_lanes
        pipe = SplitPipeline(parallelism=lanes, depth=lanes, stage="scan")
        wait = mesh_metrics().histogram("feeder_wait_ms")
        # changelog splits have no merge to batch: read on the consumer
        data = [i for i, s in enumerate(splits) if not s.is_changelog]
        round_of = {i: n // lanes for n, i in enumerate(data)}
        round_ends = {round_of[i]: i for i in data}  # a round's last split
        # the split whose dispatch has to be in before split i resolves
        need = [round_ends[round_of[i]] if i in round_of else i for i in range(len(splits))]

        def dispatch(i: int):
            if i not in round_of:
                return None
            with mex.active(round_of[i]):
                return self._dispatch(splits[i])

        it = pipe.map_ordered(range(len(splits)), dispatch)
        conts: list = []
        try:
            for i, s in enumerate(splits):
                # a split resolves once its whole round has dispatched
                while len(conts) <= need[i]:
                    with span("mesh.feed", histogram=wait, shards=lanes):
                        conts.append(next(it))
                cont, conts[i] = conts[i], None
                yield self.read(s) if cont is None else cont(sink)
        finally:
            it.close()

    def read_all(self, splits: Sequence[DataSplit]):
        """Every split's rows in one batch. Several splits build it once, in
        a BatchSink sized by the plan: a split that can writes its winners
        into their rows of it (batches), any other batch is copied in as it
        arrives, and what could not be sized beforehand is joined at the end,
        a column a task on the shared pool. Under a predicate or a limit the
        plan's rows bound nothing the result comes near: no array is sized,
        and the join at the end is of every column."""
        from ..data.batch import BatchSink, ColumnBatch
        from ..metrics import read_metrics, span
        from ..parallel.pipeline import bounded_map

        splits = list(splits)
        rows_in = sum(s.row_count for s in splits)
        schema = self.table.row_type if self.projection is None else self.table.row_type.project(self.projection)
        placed = joined = 0
        # one operation: every span below, on this thread or a pool's,
        # carries the id allotted here
        with span("read_all", new_op=True, splits=len(splits), rows_in=rows_in) as sp:
            if len(splits) > 1:
                with span("concat", rows=0, columns=len(schema.fields)):  # the result's arrays, mapped
                    sink = BatchSink(schema, rows_in if self.predicate is None and self.limit is None else None)
                for b in self.batches(splits, sink):
                    with span("concat", rows=b.num_rows, columns=len(schema.fields)):
                        sink.append(b)
                parallelism = self.table.store.pipeline_config()[1]
                with span("concat", rows=sink.rows, columns=len(schema.fields)):
                    out = sink.result(lambda fn, names: bounded_map(fn, names, parallelism))
                placed, joined = sink.placed, sink.joined
            else:
                only = list(self.batches(splits))  # no split, or the one split's batch as it is
                out = only[0] if only else ColumnBatch.empty(schema)
            sp.add(rows_out=out.num_rows)
        g = read_metrics()
        g.counter("ops").inc()
        g.counter("rows_in").inc(rows_in)
        g.counter("rows_out").inc(out.num_rows)
        g.counter("rows_placed").inc(placed)
        g.counter("rows_joined").inc(joined)
        return out
