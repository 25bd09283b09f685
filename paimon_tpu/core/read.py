"""Merge-on-read execution: sections -> device merge -> filtered batches.

Parity: /root/reference/paimon-core/.../operation/MergeFileSplitRead.java
(createMergeReader:246-284; the predicate split rule :184-221 — only key
filters may skip files/row-groups of overlapping sections, value filters must
run after merging so a new version can still shadow an old one) and
RawFileSplitRead.java:69 (no-merge path for single-run sections).
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from ..data.batch import BatchSink, ColumnBatch, concat_batches
from ..data.predicate import Predicate, PredicateBuilder, and_
from ..metrics import read_metrics, span
from .datafile import DataFileMeta, KeyValueFileReaderFactory
from .kv import KVBatch
from .levels import IntervalPartition
from .mergefn import MergeExecutor, gather_winners

__all__ = ["MergeFileSplitRead", "order_runs_for_merge"]


_arrow_decode_warm = False


def _ensure_arrow_decode_initialized():
    """One tiny in-memory parquet roundtrip on the CALLING thread before any
    threaded decode: pyarrow's lazily-initialized process globals (thread
    pools, codecs, kernel registries) segfault — reproducibly on this
    single-core rig — when their first-ever initialization races across two
    pool threads both entering read_row_groups. ~1ms, once per process."""
    global _arrow_decode_warm
    if _arrow_decode_warm:
        return
    import io as _io

    import pyarrow as pa
    import pyarrow.parquet as pq

    buf = _io.BytesIO()
    pq.write_table(pa.table({"x": [0]}), buf)
    buf.seek(0)
    pq.ParquetFile(buf).read()
    _arrow_decode_warm = True


def _parallel_map(fn, items, parallelism: int | None = None):
    """Decode several files concurrently (pyarrow/zstd release the GIL, so
    threads give real parallelism on the host-side columnar decode — the
    stage that dominates once the device downloads are compact). Runs on the
    process-wide shared pool (utils.shared_executor): a pool per call paid
    thread spawn/teardown on every split, measurable on small files. Order
    is preserved; single-item lists skip the pool. `parallelism` bounds the
    in-flight window (the scan.parallelism option; None = pool width,
    1 = strictly serial)."""
    items = list(items)
    if len(items) <= 1 or (parallelism is not None and parallelism <= 1):
        return [fn(x) for x in items]
    _ensure_arrow_decode_initialized()
    from ..parallel.pipeline import bounded_map

    return bounded_map(fn, items, parallelism)


def order_runs_for_merge(section) -> tuple[list, bool]:
    """Order a section's runs by ascending sequence range and report whether
    the ranges are pairwise disjoint. Disjoint + ordered means equal keys
    appear in ascending seq order after concatenation, so the merge kernel
    can rely on sort stability instead of uploading sequence lanes."""
    runs = sorted(section, key=lambda r: min(f.min_sequence_number for f in r.files))
    disjoint = True
    prev_max = None
    for r in runs:
        lo = min(f.min_sequence_number for f in r.files)
        hi = max(f.max_sequence_number for f in r.files)
        if prev_max is not None and lo <= prev_max:
            disjoint = False
            break
        prev_max = hi
    return runs, disjoint


class MergeFileSplitRead:
    def __init__(
        self,
        reader_factory: KeyValueFileReaderFactory,
        merge_executor: MergeExecutor,
        key_names: Sequence[str],
        parallelism: int | None = None,
    ):
        self.reader_factory = reader_factory
        self.merge = merge_executor
        self.key_names = set(key_names)
        # scan.parallelism: in-flight bound of the per-file decode fan-out
        self.parallelism = parallelism

    def read_split(
        self,
        files: list[DataFileMeta],
        predicate: Predicate | None = None,
        projection: Sequence[str] | None = None,
        drop_delete: bool = True,
        deletion_vectors: dict | None = None,
    ) -> ColumnBatch:
        """Merge-read one bucket's files. Returns the value rows (projected),
        key-sorted within each section."""
        return self.read_split_dispatch(files, predicate, projection, drop_delete, deletion_vectors)()

    def read_split_dispatch(
        self,
        files: list[DataFileMeta],
        predicate: Predicate | None = None,
        projection: Sequence[str] | None = None,
        drop_delete: bool = True,
        deletion_vectors: dict | None = None,
    ):
        """Phase 1 of the (possibly mesh-batched) merge-read: read the
        section inputs and dispatch their merges; returns the continuation
        producing the final ColumnBatch. Under an active
        mesh context (parallel/mesh_exec.py), the merges of every split
        dispatched in the same round execute in family-batched shard_maps
        over the mesh's bucket axis — the TPU equivalent of the reference
        shipping one split per task (MergeTreeSplitGenerator.java:38).

        The continuation takes the BatchSink that its caller appends the
        batch to next, if there is one: a split that can then writes its
        winners where they belong (_complete); the batch is the same."""
        key_parts = []
        if predicate is not None:
            parts = PredicateBuilder.split_and(predicate)
            key_parts = PredicateBuilder.pick_by_fields(parts, self.key_names)
        key_filter = and_(*key_parts) if key_parts else None

        dvs = deletion_vectors or {}
        with span("split", files=len(files)) as sp:
            sections = IntervalPartition(files).partition()
            sp.add(sections=len(sections))
            section_conts = [self._dispatch_section(section, predicate, key_filter, dvs) for section in sections]

        def complete(sink: BatchSink | None = None) -> ColumnBatch:
            with span("split", files=len(files), sections=len(sections)):
                return self._complete(section_conts, predicate, projection, drop_delete, sink)

        return complete

    def _dispatch_section(self, section, predicate, key_filter, dvs: dict):
        """Read one section's inputs and dispatch its merge; returns the
        continuation that gives the section's merged KVBatch. It takes the
        place _complete has for the rows, which only the keys-only
        pipeline's gather can use."""
        if len(section) == 1:
            # single sorted run: keys are unique — no merge needed; full
            # predicate pushdown is safe (reference RawFileSplitRead)
            kv = self._read_files(section[0].files, predicate, dvs)
            return lambda place=None: kv
        runs, seq_ascending = order_runs_for_merge(section)
        ordered_files = [f for run in runs for f in run.files]
        has_dv = any(f.file_name in dvs for f in ordered_files)
        if self.merge.supports_keys_only_pipeline() and not has_dv:
            # resolved by the caller: on a single device the host decode has
            # overlapped the device sort; under a mesh context the select is
            # a job of the round's shard_map, which runs once every split of
            # the round has dispatched
            return self._pipelined_dedup(ordered_files, key_filter, seq_ascending)
        # deletion vectors and the engines that merge whole batches
        kv = self._read_files(ordered_files, key_filter, dvs)
        handle = self.merge.merge_async(kv, seq_ascending=seq_ascending)
        return lambda place=None: self.merge.merge_resolve(handle)

    def _read_files(self, files, predicate, dvs: dict) -> KVBatch:
        """Whole files, concatenated in the order given: the per-file reads
        fan out over the shared pool (order preserved, so the concatenated
        runs and the merge output are bit-identical to serial)."""
        with span("decode.all", files=len(files)):
            parts = _parallel_map(lambda f: self._read_file(f, predicate, dvs), files, parallelism=self.parallelism)
        with span("concat", rows=sum(p.num_rows for p in parts), columns=len(self.reader_factory.read_schema.fields)):
            return KVBatch.concat(parts)

    def _complete(self, section_conts, predicate, projection, drop_delete: bool, sink: BatchSink | None = None) -> ColumnBatch:
        """Phase 2: resolve every section's merge, then drop deletes, apply
        the predicate and the projection, and concatenate the sections.
        `sink`: the result the caller appends this batch to next. Several
        sections are joined and a predicate filters; otherwise the gather's
        rows stay where they are written, so it gets the sink as its place."""
        place = sink if len(section_conts) == 1 and predicate is None else None
        out: list[ColumnBatch] = []
        for cont in section_conts:
            kv = cont(place)
            with span("finish", rows=kv.num_rows):
                if drop_delete:
                    kv = kv.drop_deletes()
                data = kv.data
                if predicate is not None and data.num_rows:
                    mask = predicate.eval(data)
                    if not mask.all():
                        data = data.filter(mask)
                if projection is not None:
                    data = data.select(projection)
            out.append(data)
        if not out:
            schema = self.reader_factory.read_schema
            if projection is not None:
                schema = schema.project(projection)
            return ColumnBatch.empty(schema)
        with span("concat", rows=sum(b.num_rows for b in out), columns=len(out[0].schema.fields)):
            return concat_batches(out)

    def _read_file(self, f: DataFileMeta, predicate, dvs: dict) -> KVBatch:
        """Read one file, applying its deletion vector if present. DV
        positions are absolute file row positions, so a DV'd file is read
        without row-group skipping (which would shift positions)."""
        dv = dvs.get(f.file_name)
        if dv is None:
            return self.reader_factory.read(f, predicate=predicate)
        kv = self.reader_factory.read(f, predicate=None)
        mask = ~dv.deleted_mask(kv.num_rows)
        return kv.filter(mask) if not mask.all() else kv

    def _pipelined_dedup(self, ordered_files, key_filter, seq_ascending: bool):
        """Overlap host decode with the device merge: decode just the key
        columns, dispatch the dedup kernel (async), decode the value columns
        while the device sorts; the continuation returned resolves the
        select and gathers the winners from the per-file value columns,
        into `place` where it is given one (mergefn.gather_winners).
        The two decode passes share the predicate, so their row sets are
        identical (datafile.read contract)."""
        key_names = [n for n in self.reader_factory.read_schema.field_names if n in self.key_names]
        rest_names = [n for n in self.reader_factory.read_schema.field_names if n not in self.key_names]
        # run stability replaces sequence comparison when seq ranges are
        # disjoint+ordered: skip decoding _SEQUENCE_NUMBER (random int64 is
        # the costliest system column) and read only _VALUE_KIND
        sys_cols = "kind" if seq_ascending else True
        with span("decode.keys", files=len(ordered_files)):
            heads = _parallel_map(
                lambda f: self.reader_factory.read(f, predicate=key_filter, fields=key_names, system_columns=sys_cols),
                ordered_files,
                parallelism=self.parallelism,
            )
        with span("concat", rows=sum(h.num_rows for h in heads), columns=len(key_names)):
            kv_keys = KVBatch.concat(heads)
        if kv_keys.num_rows == 0:
            empty = KVBatch(
                ColumnBatch.empty(self.reader_factory.read_schema),
                np.empty(0, dtype=np.int64),
                np.empty(0, dtype=np.uint8),
            )
            return lambda place=None: empty
        # file -> run offsets for key-range tiling (files of one run are
        # consecutive in ordered_files and key-sorted)
        run_offsets = [0]
        for h in heads:
            run_offsets.append(run_offsets[-1] + h.num_rows)
        handle = self.merge.dedup_select_async(kv_keys, seq_ascending, run_offsets=run_offsets)
        tails = []
        if rest_names:
            with span("decode.values", files=len(ordered_files)):
                tails = _parallel_map(
                    lambda f: self.reader_factory.read(
                        f, predicate=key_filter, fields=rest_names, system_columns=False
                    ),
                    ordered_files,
                    parallelism=self.parallelism,
                )
        pool_map = lambda fn, items: _parallel_map(fn, items, self.parallelism)  # noqa: E731
        return lambda place=None: gather_winners(
            kv_keys, tails, run_offsets, self.merge.dedup_resolve(handle), self.reader_factory.read_schema, read_metrics(),
            pool_map, place,
        )

    def read_kv(
        self, files: list[DataFileMeta], drop_delete: bool = False, deletion_vectors: dict | None = None
    ) -> KVBatch:
        """Raw merged KeyValues (used by compaction tests / changelog)."""
        dvs = deletion_vectors or {}
        sections = IntervalPartition(files).partition()
        parts: list[KVBatch] = []
        for section in sections:
            runs, seq_ascending = order_runs_for_merge(section)
            batches = [self._read_file(f, None, dvs) for run in runs for f in run.files]
            kv = KVBatch.concat(batches)
            if len(section) > 1:
                kv = self.merge.merge(kv, seq_ascending=seq_ascending)
            if drop_delete:
                kv = kv.drop_deletes()
            parts.append(kv)
        return KVBatch.concat(parts) if parts else KVBatch(
            ColumnBatch.empty(self.reader_factory.read_schema),
            np.empty(0, dtype=np.int64),
            np.empty(0, dtype=np.uint8),
        )
