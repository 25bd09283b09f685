"""Merge-engine orchestration over the device kernel.

Parity: /root/reference/paimon-core/.../mergetree/compact/MergeFunction.java
hierarchy — DeduplicateMergeFunction, FirstRowMergeFunction,
PartialUpdateMergeFunction.java:57, AggregateMergeFunction + factories.
One MergeExecutor call is the batch equivalent of feeding every same-key group
through the reference's reset/add/getResult loop: encode keys, run the sort
plan on device, apply the engine as segment selections/reductions, and emit
one key-sorted output row per key.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from ..data.batch import BatchSink, Column, ColumnBatch, PartsTake
from ..data.keys import build_string_pool, encode_key_lanes, split_int64_lanes
from ..metrics import MetricGroup, aggregate_metrics, partial_update_metrics, read_metrics, span
from ..options import CoreOptions, MergeEngine
from ..ops import (
    AggregateSpec,
    aggregate_merge,
    deduplicate_select,
    deduplicate_take,
    first_row_take,
    merge_plan,
    partial_update_takes,
)
from ..ops.aggregates import _gather_column, field_on, takes_scan
from ..types import RowKind, RowType, TypeRoot
from .kv import KVBatch, retracts

__all__ = ["MergeExecutor", "gather_winners"]


def gather_winners(
    kv_keys: KVBatch,
    parts: Sequence[KVBatch],
    offsets: Sequence[int],
    take: np.ndarray,
    schema: RowType,
    counters: MetricGroup,
    map_fn,
    place: BatchSink | None = None,
) -> KVBatch:
    """The winners of a dedup merge that dispatched on keys alone, a column
    a task of `map_fn` (the shared pool's ordered map). `kv_keys` is the
    parts' key columns, seq and kind, joined in part order, the parts
    starting at row `offsets` (len(parts) + 1 of them); `take` indexes it. A
    value column of `schema` is taken straight from its per-file parts
    (Column.take_from_parts: the parts are never concatenated); the key
    columns and seq, which were joined for the lanes, are taken whole. Same
    batch as the concatenation's take would give. `counters` gets
    rows_gathered and rows_gathered_from_parts (read{...} for the read path,
    compaction{...} for a rewrite).

    kind goes first, on this thread: where no winner is a -D or -U, a
    numpy-valued column of the schema is written into the rows that `place`
    reserves (the read path's result; a rewrite gives none): its values are
    then a view of that result, which an append leaves where they are."""
    key_names = set(kv_keys.data.schema.field_names)
    rows_out = len(take)
    with span("gather", rows_in=kv_keys.num_rows, rows_out=rows_out, columns=len(schema.fields), parts=len(offsets) - 1):
        plan = None
        if parts:
            with span("gather.plan", rows=rows_out, parts=len(parts)):
                plan = PartsTake(offsets, take, map_fn)
        with span("gather.column", column="_kind", rows_out=rows_out, parts=1):
            kind = kv_keys.kind.take(take)  # raises for a take beyond the keys
        dest = place.reserve(rows_out) if place is not None and not retracts(kind).any() else {}
        # a task a column: a key column or seq whole, or a value column's per-file parts; only a column
        # of the schema has a destination, whatever its name
        tasks = [
            (n, kv_keys.data.column(n) if n in key_names else [p.data.column(n) for p in parts], dest.get(n))
            for n in schema.field_names
        ]
        tasks.append(("_seq", Column(kv_keys.seq), None))

        def gather_column(task):
            name, col, out = task
            whole = not isinstance(col, list)
            with span("gather.column", column=name, rows_out=rows_out, parts=1 if whole else len(col)):
                if not whole:
                    return Column.take_from_parts(col, plan, out)
                plain = col._values is not None and col.dict_cache is None  # values and validity, nothing else to carry
                if out is None or not plain or col._values.dtype != out.dtype:
                    return col.take(take), False
                # kind's take has checked the indices: numpy writes straight into `out` only
                # under a mode that cannot raise halfway
                values = np.take(col._values, take, out=out, mode="wrap")
                return Column(values, None if col.validity is None else col.validity.take(take)), False

        gathered = map_fn(gather_column, tasks)
        *cols, seq = (col for col, _ in gathered)
        counters.counter("rows_gathered").inc(rows_out * (len(tasks) + 1))
        counters.counter("rows_gathered_from_parts").inc(rows_out * sum(from_parts for _, from_parts in gathered))
    return KVBatch(ColumnBatch(schema, cols), seq.values, kind)


def _count_partial_update(rows_in: int, rows_out: int, fields: int) -> None:
    g = partial_update_metrics()
    g.counter("merges").inc()
    g.counter("rows_in").inc(rows_in)
    g.counter("rows_out").inc(rows_out)
    g.counter("fields").inc(fields)


def _count_aggregate(rows_in: int, rows_out: int, fields: int, fields_on_host: int, fields_by_scan: int) -> None:
    g = aggregate_metrics()
    g.counter("merges").inc()
    g.counter("rows_in").inc(rows_in)
    g.counter("rows_out").inc(rows_out)
    g.counter("fields").inc(fields)
    g.counter("fields_on_host").inc(fields_on_host)
    g.counter("fields_by_scan").inc(fields_by_scan)


def _numpy_dedup_select(
    lanes: np.ndarray, seq_lanes: np.ndarray | None, compress: bool | None = None, plan=None
) -> np.ndarray:
    """sort-engine=numpy: the pure-host oracle path (useful when no
    accelerator is attached, and as the reference implementation the device
    kernels are tested against). Lane compression applies here too — fewer
    lexsort key arrays and fewer boundary compares, same selection — with an
    all-constant key short-circuiting to the scalar winner. Lanes that come
    with their `plan` are packed already."""
    from ..data.keys import lexsort_rows
    from ..ops.lanes import compress_key_lanes, scalar_dedup_winner

    n = lanes.shape[0]
    if plan is None:
        lanes, plan = compress_key_lanes(lanes, compress, enable_ovc=False)
    if plan is not None and lanes.shape[1] == 0:
        return scalar_dedup_winner(seq_lanes, n)
    tiebreakers = [] if seq_lanes is None else [seq_lanes[:, i] for i in range(seq_lanes.shape[1])]
    order = lexsort_rows(lanes, *tiebreakers)
    sorted_lanes = lanes[order]
    neq = (sorted_lanes[1:] != sorted_lanes[:-1]).any(axis=1)
    keep_last = np.concatenate([neq, np.ones(1, dtype=np.bool_)])
    return order[keep_last]


class MergeExecutor:
    def __init__(
        self,
        value_schema: RowType,
        key_names: Sequence[str],
        engine: MergeEngine = MergeEngine.DEDUPLICATE,
        options: CoreOptions | None = None,
    ):
        self.value_schema = value_schema
        self.key_names = list(key_names)
        self.engine = engine
        self.options = options or CoreOptions()
        self._string_keys = [
            k
            for k in self.key_names
            if value_schema.field(k).type.root in (TypeRoot.CHAR, TypeRoot.VARCHAR, TypeRoot.BINARY, TypeRoot.VARBINARY)
        ]
        self._user_seq = self.options.sequence_field

    @property
    def _compress(self) -> bool:
        """merge.lane-compression: the key-lane compression layer (the
        PAIMON_TPU_LANE_COMPRESSION env var overrides at the ops seam)."""
        return self.options.lane_compression

    def effective_sort_engine(self):
        """The merge backend actually used. sort-engine set on the table
        wins unconditionally (a table that explicitly chose numpy keeps
        it); then PAIMON_TPU_FORCE_DEVICE_ENGINE=1 pins the device kernel so
        the test suite exercises the dispatch path on its virtual-CPU mesh;
        otherwise the default ADAPTS to the resolved platform: the host
        lexsort path on a CPU-only backend (a single stable `np.lexsort`
        beats XLA:CPU's variadic stable sort ~3x at the 1M-row scale), the
        device kernel everywhere else. The check never initializes a
        backend (ops.merge.resolved_platform_is_cpu)."""
        import os

        from ..options import CoreOptions, SortEngine

        if self.options.options.contains(CoreOptions.SORT_ENGINE):
            return self.options.sort_engine
        if os.environ.get("PAIMON_TPU_FORCE_DEVICE_ENGINE", "") == "1":
            return SortEngine.XLA_SEGMENTED
        from ..ops.merge import resolved_platform_is_cpu

        if resolved_platform_is_cpu():
            return SortEngine.NUMPY
        return SortEngine.XLA_SEGMENTED

    def _key_lanes(self, kv: KVBatch) -> np.ndarray:
        from ..data.keys import encode_key_lanes_with_pools

        with span("lanes.encode", rows=kv.num_rows) as sp:
            lanes = encode_key_lanes_with_pools(kv.data, self.key_names)
            sp.add(lanes=lanes.shape[1])
        return lanes

    def _sort_lanes(self, kv: KVBatch, enable_ovc: bool):
        """(key lanes, LanePlan or None) for a deduplicate dispatcher. Where
        every key column is a plain integer column the sort operands are
        planned and packed straight from the columns
        (ops.lanes.compress_key_columns) and come with their plan, which
        tells the dispatcher not to pack again; any other key, a plan with an
        OVC lane, or the layer off, gives the raw (n, K) matrix and None."""
        from ..data.keys import integer_key_columns
        from ..ops.lanes import compress_key_columns

        columns = integer_key_columns(kv.data, self.key_names)
        if columns is not None:
            with span("lanes.encode", rows=kv.num_rows) as sp:
                packed = compress_key_columns(columns, self._compress, enable_ovc)
                if packed is not None:
                    sp.add(packed=1, lanes=packed[0].shape[1])
                    return packed
        return self._key_lanes(kv), None

    def _lanes(self, kv: KVBatch, seq_ascending: bool) -> tuple[np.ndarray, np.ndarray | None]:
        return self._key_lanes(kv), self._seq_lanes(kv, seq_ascending)

    def _seq_lanes(self, kv: KVBatch, seq_ascending: bool) -> np.ndarray | None:
        if seq_ascending and not self._user_seq:
            return None
        with span("lanes.encode", rows=kv.num_rows) as sp:
            lanes = self._encode_seq_lanes(kv, seq_ascending)
            sp.add(lanes=lanes.shape[1])
        return lanes

    def _encode_seq_lanes(self, kv: KVBatch, seq_ascending: bool) -> np.ndarray:
        seq_parts = []
        if self._user_seq:
            # user-defined sequence fields order before the system seqno
            # (reference: MergeSorter orders by (key, udsSeq, seqNumber))
            from ..data.keys import exact_string_pool

            useq_pools = {
                f: exact_string_pool([kv.data.column(f)])
                for f in self._user_seq
                if kv.data.schema.field(f).type.root in (TypeRoot.CHAR, TypeRoot.VARCHAR)
            }
            seq_parts.append(encode_key_lanes(kv.data, self._user_seq, useq_pools))
        if not seq_ascending:
            # explicit seqno lanes only when input order doesn't already
            # encode them (stability of the device sort covers the rest)
            hi, lo = split_int64_lanes(kv.seq)
            seq_parts.append(np.stack([hi, lo], axis=1))
        return np.concatenate(seq_parts, axis=1)

    @staticmethod
    def _strictly_increasing(lanes: np.ndarray) -> bool:
        """O(n) host check: are the key tuples strictly ascending? Compare
        lane-wise: row i < row i+1 lexicographically for every i."""
        if lanes.shape[0] <= 1:
            return True
        a, b = lanes[:-1], lanes[1:]
        k = lanes.shape[1]
        lt = np.zeros(len(a), dtype=np.bool_)
        eq = np.ones(len(a), dtype=np.bool_)
        for i in range(k):
            lt |= eq & (a[:, i] < b[:, i])
            eq &= a[:, i] == b[:, i]
        return bool(lt.all())

    def _plan(self, kv: KVBatch, seq_ascending: bool = False):
        lanes, seq_lanes = self._lanes(kv, seq_ascending)
        return merge_plan(lanes, seq_lanes, compress=self._compress)

    def merge(self, kv: KVBatch, seq_ascending: bool = False) -> KVBatch:
        """One output row per key, key-sorted. Dedup keeps the winning row's
        RowKind (a -D survives compaction until the top level); partial-update
        and aggregation emit +I rows.

        seq_ascending=True asserts that rows with equal keys appear in
        ascending sequence-number order in the input (true for memtable
        flushes and for runs with disjoint seq ranges concatenated in seq
        order) — the kernel then skips uploading sequence lanes entirely.
        """
        return self.merge_resolve(self.merge_async(kv, seq_ascending))

    def merge_async(self, kv: KVBatch, seq_ascending: bool = False):
        """Dispatch half of merge(). When a mesh context is active (a
        MeshExecutor or one of its rounds), the bucket's merge becomes a job
        — the jobs of one round, or of one batch window, run in family-batched
        shard_maps over the mesh at the first resolve; without a context the
        merge computes eagerly inside the handle. One
        copy of the preamble (ignore-delete, sorted-unique shortcut, lane
        encoding) serves both paths, so mesh and single-device execution
        cannot diverge. Resolve with merge_resolve()."""
        from ..options import SortEngine
        from ..parallel.mesh_exec import current_mesh_context

        ctx = current_mesh_context()
        if kv.num_rows == 0:
            return ("sync", kv)
        if self.options.ignore_delete:
            keep = kv.kind != int(RowKind.DELETE)
            if not keep.all():
                kv = kv.filter(keep)
                if kv.num_rows == 0:
                    return ("sync", kv)
        if self.engine == MergeEngine.DEDUPLICATE:
            select = self._dedup_dispatch(kv, seq_ascending)
            if select is None:
                return ("sync", kv)
            tag, h = select
            if tag == "numpy":
                return ("sync", kv.take(h))
            if tag == "mesh":
                return ("dedup", *h, kv)
            return ("sync", self.gather(kv, self.dedup_resolve(select)))
        lanes, seq_lanes = self._lanes(kv, seq_ascending)
        engine = self.effective_sort_engine()
        if ctx is not None and engine != SortEngine.NUMPY:
            from ..ops.lanes import resolve_compress

            return (
                "plan",
                ctx,
                ctx.submit_plan(lanes, seq_lanes, compress=resolve_compress(self._compress)),
                kv,
            )
        if engine != SortEngine.NUMPY:
            # single-device fast paths: sort + segment + engine selection in
            # ONE kernel call (no plan download, no per-field round trips)
            if self.engine == MergeEngine.PARTIAL_UPDATE and not self._sequence_groups():
                return ("sync", self._partial_update_fused(kv, lanes, seq_lanes))
            if self.engine == MergeEngine.AGGREGATE:
                from ..ops.aggregates import fused_routable

                fields = [f for f in self.value_schema.fields if f.name not in self.key_names]
                specs = [self._agg_spec(f.name) for f in fields]
                cols = [kv.data.column(f.name) for f in fields]
                if fused_routable(specs, cols):
                    return ("sync", self._aggregate_fused(kv, lanes, seq_lanes, fields, specs, cols))
        return ("sync", self._merge_with_plan(kv, merge_plan(lanes, seq_lanes, compress=self._compress)))

    def _dedup_dispatch(self, kv: KVBatch, seq_ascending: bool):
        """The dedup merge's device half on whatever `kv` holds of the key
        columns, seq and kind (the lanes read nothing else): None where the
        keys already strictly increase, else a dedup_resolve handle: the
        host engine's winners, a job of the mesh context, or the stream
        tiles of deduplicate_select_async."""
        from ..options import SortEngine
        from ..parallel.mesh_exec import current_mesh_context

        ctx = current_mesh_context()
        engine = self.effective_sort_engine()
        if ctx is not None and engine != SortEngine.NUMPY:
            lanes, plan = self._key_lanes(kv), None  # a round packs its shards itself
        else:
            lanes, plan = self._sort_lanes(kv, enable_ovc=engine != SortEngine.NUMPY)
        if self._strictly_increasing(lanes):
            # already key-sorted with unique keys (bulk loads, replayed
            # sorted runs): dedup is the identity — skip the device trip
            # (sequence lanes are never built on this path; packing keeps
            # order and equality, so packed lanes answer as raw ones do)
            return None
        seq_lanes = self._seq_lanes(kv, seq_ascending)
        if engine == SortEngine.NUMPY:
            return ("numpy", _numpy_dedup_select(lanes, seq_lanes, self._compress, plan))
        if ctx is not None:
            # submit RAW lanes — compression is decided ONCE per family
            # batch from stats reduced over every shard
            # (ops.lanes.plan_lanes_global), so all shards of one
            # shard_map agree on packed widths (ISSUE 7 fix)
            from ..ops.lanes import resolve_compress

            return ("mesh", (ctx, ctx.submit_dedup(lanes, seq_lanes, compress=resolve_compress(self._compress))))
        from ..ops.merge import deduplicate_select_async

        return ("single", deduplicate_select_async(lanes, seq_lanes, compress=self._compress, plan=plan))

    def merge_parts_async(self, kv_keys: KVBatch, parts: Sequence[KVBatch], offsets: Sequence[int],
                          seq_ascending: bool, counters: MetricGroup):
        """merge_async for a dedup merge whose value columns stay in their
        per-file parts (where supports_keys_only_pipeline()): the dispatch
        runs on `kv_keys`, the parts' key columns, seq and kind joined at
        row `offsets`, exactly as merge_async runs it on a whole batch (the
        same lanes, sorted-keys shortcut, stream tiles resolved here, or job
        of the mesh context); merge_resolve gathers the winners from the
        parts (gather_winners, counting into `counters`), or, where the keys
        already strictly increase, joins the parts whole."""
        select = self._dedup_dispatch(kv_keys, seq_ascending) if kv_keys.num_rows else None
        if select is not None and select[0] == "single":
            # resolved here, as merge_async resolves it: the take in hand, as the host engine's is
            select = ("numpy", self.dedup_resolve(select))
        return ("parts", select, kv_keys, parts, offsets, counters)

    def merge_resolve(self, handle) -> KVBatch:
        tag = handle[0]
        if tag == "sync":
            return handle[1]
        if tag == "parts":
            return self._resolve_parts(*handle[1:])
        _, ctx, job_id, kv = handle
        if tag == "dedup":
            return self.gather(kv, ctx.result(job_id))
        return self._merge_with_plan(kv, ctx.result(job_id))

    def _resolve_parts(self, select, kv_keys: KVBatch, parts, offsets, counters: MetricGroup) -> KVBatch:
        from ..parallel.pipeline import bounded_map

        arrays = len(self.value_schema.fields) + 2
        if select is None:
            # every row wins: the parts joined, as the whole-batch path hands them on
            with span("gather", rows_in=kv_keys.num_rows, rows_out=kv_keys.num_rows, columns=arrays - 2,
                      parts=len(parts)):
                out = KVBatch.concat(parts)
            counters.counter("rows_gathered").inc(out.num_rows * arrays)
            return out
        return gather_winners(kv_keys, parts, offsets, self.dedup_resolve(select), self.value_schema, counters,
                              bounded_map)

    @staticmethod
    def gather(kv: KVBatch, take: np.ndarray) -> KVBatch:
        columns = len(kv.data.schema.fields)
        with span("gather", rows_in=kv.num_rows, rows_out=len(take), columns=columns):
            out = kv.take(take)
        # the whole batch was concatenated: every column, seq and kind, none from parts
        read_metrics().counter("rows_gathered").inc(len(take) * (columns + 2))
        return out

    def supports_keys_only_pipeline(self) -> bool:
        """True when merge needs only (key cols, seq, kind) to pick winners —
        lets the read path dispatch the kernel before value columns decode."""
        return self.engine == MergeEngine.DEDUPLICATE and not self.options.ignore_delete and not self._user_seq

    def dedup_select_async(self, kv_keys: KVBatch, seq_ascending: bool, run_offsets=None):
        """kv_keys carries only the key columns. Returns an opaque handle.
        With run_offsets and no explicit seq lanes, dispatches key-range tiles
        so transfers of one tile overlap the device sort of another. On the
        host engine (explicit or platform-adaptive) the select runs
        synchronously — same handle contract, no device round trip. Under a
        MeshExecutor (a reader's round) the select is a job of the round's
        shard_map: RAW lanes, as merge_async submits them, and no tiling —
        a round is the tile. Everywhere else the key lanes come packed from
        the key columns where those are integers (_sort_lanes), so the
        (n, K) matrix is built only for a consumer of one."""
        from ..options import SortEngine
        from ..parallel.mesh_exec import current_mesh_context

        seq_lanes = self._seq_lanes(kv_keys, seq_ascending)
        engine = self.effective_sort_engine()
        if engine == SortEngine.NUMPY:
            lanes, plan = self._sort_lanes(kv_keys, enable_ovc=False)
            return ("numpy", _numpy_dedup_select(lanes, seq_lanes, self._compress, plan))
        ctx = current_mesh_context()
        if ctx is not None:
            from ..ops.lanes import resolve_compress

            lanes = self._key_lanes(kv_keys)
            return ("mesh", (ctx, ctx.submit_dedup(lanes, seq_lanes, compress=resolve_compress(self._compress))))
        from ..ops.merge import deduplicate_select_async, deduplicate_tiled_dispatch

        lanes, plan = self._sort_lanes(kv_keys, enable_ovc=True)
        if seq_lanes is None and run_offsets is not None:
            tile_rows = self.options.options.get(CoreOptions.MERGE_READ_BATCH_ROWS)
            # the tiled dispatcher owns the compression seam (one plan per
            # merge, shared by every tile) and the all-constant fast path
            return (
                "tiled",
                deduplicate_tiled_dispatch(lanes, run_offsets, tile_rows, compress=self._compress, plan=plan),
            )
        return ("single", deduplicate_select_async(lanes, seq_lanes, compress=self._compress, plan=plan))

    @staticmethod
    def dedup_resolve(handle) -> np.ndarray:
        tag, h = handle
        if tag == "numpy":
            return h
        if tag == "mesh":
            ctx, job_id = h
            return ctx.result(job_id)
        from ..ops.merge import deduplicate_resolve, deduplicate_resolve_tiled

        return deduplicate_resolve_tiled(h) if tag == "tiled" else deduplicate_resolve(h)

    def _merge_with_plan(self, kv: KVBatch, plan) -> KVBatch:
        if self.engine == MergeEngine.FIRST_ROW:
            if np.isin(kv.kind, (int(RowKind.UPDATE_BEFORE), int(RowKind.DELETE))).any():
                raise ValueError("first-row merge engine accepts only +I/+U records")
            return kv.take(first_row_take(plan))

        last_take = plan.perm[plan.keep_last & plan.valid_sorted]
        out_seq = kv.seq.take(last_take)

        if self.engine == MergeEngine.PARTIAL_UPDATE:
            return self._partial_update(kv, plan, last_take, out_seq)
        if self.engine == MergeEngine.AGGREGATE:
            return self._aggregate(kv, plan, last_take, out_seq)
        raise ValueError(f"unknown merge engine {self.engine}")

    # ---- partial update -------------------------------------------------
    def _sequence_groups(self) -> dict[str, list[str]]:
        """{seq-column: [fields it governs]} from fields.<col>.sequence-group
        options (reference PartialUpdateMergeFunction sequence groups)."""
        groups: dict[str, list[str]] = {}
        for key, value in self.options.options._data.items():
            if key.startswith("fields.") and key.endswith(".sequence-group"):
                seq_col = key[len("fields.") : -len(".sequence-group")]
                groups[seq_col] = [s.strip() for s in str(value).split(",")]
        return groups

    def _check_partial_update_deletes(self, kv: KVBatch, remove_on_delete: bool) -> None:
        has_delete = np.isin(kv.kind, (int(RowKind.DELETE), int(RowKind.UPDATE_BEFORE))).any()
        if has_delete and not remove_on_delete:
            raise ValueError(
                "partial-update cannot handle -U/-D records; set "
                "'partial-update.remove-record-on-delete' or 'ignore-delete'"
            )

    def _partial_update_fused(self, kv: KVBatch, lanes, seq_lanes) -> KVBatch:
        """Single-call partial-update (no sequence groups): the fused kernel
        returns per-field sources + existence + winners in one device trip.
        `pu.valid` times the row kinds checked and the fields' validity
        stacked, `pu.gather` the key columns, seq and each field taken."""
        from ..ops.merge import fused_partial_update

        remove_on_delete = self.options.options.get(CoreOptions.PARTIAL_UPDATE_REMOVE_RECORD_ON_DELETE)
        fields = [f for f in self.value_schema.fields if f.name not in self.key_names]
        with span("pu.valid", rows=kv.num_rows, fields=len(fields)):
            self._check_partial_update_deletes(kv, remove_on_delete)
            field_valid = (
                np.stack([kv.data.column(f.name).valid_mask() for f in fields])
                if fields
                else np.zeros((0, kv.num_rows), np.bool_)
            )
        src, exists, last_take = fused_partial_update(
            lanes,
            seq_lanes,
            field_valid,
            kv.kind,
            remove_record_on_delete=remove_on_delete,
            compress=self._compress,
        )
        with span("pu.gather", rows_in=kv.num_rows, rows_out=len(last_take), fields=len(fields)):
            cols: dict[str, Column] = {}
            for k in self.key_names:
                cols[k] = kv.data.column(k).take(last_take)
            for fi, f in enumerate(fields):
                cols[f.name] = _gather_column(kv.data.column(f.name), src[fi])
            data = ColumnBatch(self.value_schema, cols)
            # without remove-on-delete every row is +I/+U (checked above), so
            # every segment exists; with it, vanished keys stay as -D rows
            kind = np.where(exists, int(RowKind.INSERT), int(RowKind.DELETE)).astype(np.uint8)
            out = KVBatch(data, kv.seq.take(last_take), kind)
        _count_partial_update(kv.num_rows, out.num_rows, len(fields))
        return out

    def _aggregate_fused(self, kv: KVBatch, lanes, seq_lanes, fields, specs, cols_in) -> KVBatch:
        """Single-call aggregation: every column's segment reduction runs in
        the same kernel as the sort. `agg.reduce` holds the call (its lane
        packing, the kernel and the wait) and the fields taken from its
        outputs, each an `agg.field`."""
        from ..ops.aggregates import fused_aggregate

        with span("agg.reduce", rows_in=kv.num_rows, fields=len(fields), route="fused") as sp:
            agg_cols, last_take = fused_aggregate(
                lanes, seq_lanes, cols_in, specs, kv.kind, compress=self._compress,
                names=[f.name for f in fields],
            )
            cols: dict[str, Column] = {}
            for k in self.key_names:
                cols[k] = kv.data.column(k).take(last_take)
            for f, c in zip(fields, agg_cols):
                cols[f.name] = c
            data = ColumnBatch(self.value_schema, cols)
            kind = np.full(len(last_take), int(RowKind.INSERT), dtype=np.uint8)
            out = KVBatch(data, kv.seq.take(last_take), kind)
            sp.add(rows_out=out.num_rows)
        _count_aggregate(kv.num_rows, out.num_rows, len(fields), 0, sum(map(takes_scan, specs, cols_in)))
        return out

    def _partial_update(self, kv: KVBatch, plan, last_take, out_seq) -> KVBatch:
        remove_on_delete = self.options.options.get(CoreOptions.PARTIAL_UPDATE_REMOVE_RECORD_ON_DELETE)
        self._check_partial_update_deletes(kv, remove_on_delete)
        groups = self._sequence_groups()
        grouped_fields = {f for fields in groups.values() for f in fields} | set(groups)
        non_key = [f for f in self.value_schema.fields if f.name not in self.key_names]
        default_fields = [f for f in non_key if f.name not in grouped_fields]
        field_valid = (
            np.stack([kv.data.column(f.name).valid_mask() for f in default_fields])
            if default_fields
            else np.zeros((0, kv.num_rows), np.bool_)
        )
        src, exists = partial_update_takes(plan, field_valid, kv.kind, remove_record_on_delete=remove_on_delete)
        cols: dict[str, Column] = {}
        for k in self.key_names:
            cols[k] = kv.data.column(k).take(last_take)
        for fi, f in enumerate(default_fields):
            cols[f.name] = _gather_column(kv.data.column(f.name), src[fi])
        # sequence groups: each group's fields are taken atomically from the
        # row with the highest (group seq, system seq) whose group seq is
        # non-null — ordering by the group's own sequence column, not arrival
        for seq_col, fields in groups.items():
            cols.update(self._group_take(kv, seq_col, fields))
        data = ColumnBatch(self.value_schema, cols)
        kind = np.where(exists, int(RowKind.INSERT), int(RowKind.DELETE)).astype(np.uint8)
        out = KVBatch(data, out_seq, kind)
        if not exists.all() and not remove_on_delete:
            out = out.filter(exists)
        _count_partial_update(kv.num_rows, out.num_rows, len(non_key))
        return out

    def _group_take(self, kv: KVBatch, seq_col: str, fields: Sequence[str]) -> dict[str, Column]:
        from ..ops.aggregates import pick_rows

        key_lanes = self._key_lanes(kv)
        # order: (key, group seq, system seq); null group seq sorts first and
        # is excluded from candidacy
        gcol = kv.data.column(seq_col)
        g_valid = gcol.valid_mask()
        root = kv.data.schema.field(seq_col).type.root
        from ..types import TypeRoot

        gpool = None
        if root in (TypeRoot.CHAR, TypeRoot.VARCHAR):
            gpool = {seq_col: build_string_pool([gcol.values[g_valid]])}
        g_lanes = self._lanes_nullsafe(gcol, root, gpool, seq_col)
        hi, lo = split_int64_lanes(kv.seq)
        seq_lanes = np.concatenate([g_lanes, np.stack([hi, lo], axis=1)], axis=1)
        gplan = merge_plan(key_lanes, seq_lanes, compress=self._compress)
        candidate = g_valid & np.isin(kv.kind, (int(RowKind.INSERT), int(RowKind.UPDATE_AFTER)))
        src = pick_rows(gplan, candidate)
        out = {}
        out[seq_col] = _gather_column(kv.data.column(seq_col), src)
        default_fn = self.options.options.get(CoreOptions.AGGREGATE_DEFAULT_FUNC)
        for name in fields:
            # per-field aggregators INSIDE a sequence group aggregate over the
            # group's ordering (reference PartialUpdateMergeFunction supports
            # fields.<f>.aggregate-function within sequence groups, falling
            # back to fields.default-aggregate-function); fields without
            # either take the winning row's snapshot value
            fn = self.options.field_option(name, "aggregate-function") or default_fn
            if fn is not None:
                col = kv.data.column(name)
                # rows whose group sequence is null do not participate in the
                # group at all (reference isEmptySequenceGroup :150) — mask
                # them out of the aggregation via validity
                if not g_valid.all():
                    col = Column(col.values, col.valid_mask() & g_valid)
                out[name] = aggregate_merge(gplan, col, self._agg_spec(name), kv.kind)
            else:
                out[name] = _gather_column(kv.data.column(name), src)
        return out

    @staticmethod
    def _lanes_nullsafe(col: Column, root, pool, name: str) -> np.ndarray:
        """Lane-encode a possibly-null sequence column (nulls get the minimal
        lane value, so they lose every comparison)."""
        from ..data.keys import _encode_column

        valid = col.valid_mask()
        values = col.values
        if values.dtype == np.dtype(object):
            ranks = np.zeros(len(values), dtype=np.uint32)
            if valid.any():
                p = pool[name] if pool else np.unique(values[valid])
                # ranks offset by 1 so nulls (0) sort below every real value
                ranks[valid] = np.searchsorted(p, values[valid]).astype(np.uint32) + 1
            return ranks.reshape(-1, 1)
        filled = values.copy()
        filled[~valid] = 0
        lanes = np.stack(_encode_column(filled, root, None), axis=1)
        lanes[~valid] = 0
        return lanes

    # ---- aggregation ----------------------------------------------------
    def _agg_spec(self, field_name: str) -> AggregateSpec:
        fn = self.options.field_option(field_name, "aggregate-function")
        if fn is None:
            fn = self.options.options.get(CoreOptions.AGGREGATE_DEFAULT_FUNC) or "last_non_null_value"
        ignore_retract = (self.options.field_option(field_name, "ignore-retract") or "false").lower() == "true"
        delim = self.options.field_option(field_name, "list-agg-delimiter") or ","
        distinct = (self.options.field_option(field_name, "distinct") or "false").lower() == "true"
        nested_key = tuple(
            s.strip() for s in (self.options.field_option(field_name, "nested-key") or "").split(",") if s.strip()
        )
        return AggregateSpec(fn, ignore_retract, delim, distinct, nested_key)

    def _aggregate(self, kv: KVBatch, plan, last_take, out_seq) -> KVBatch:
        """The planned route: a plan in hand, each field reduced on its own
        (`aggregate_merge`), an `agg.field` a field inside `agg.reduce`."""
        fields = [f for f in self.value_schema.fields if f.name not in self.key_names]
        on_host = by_scan = 0
        with span("agg.reduce", rows_in=kv.num_rows, rows_out=len(last_take), fields=len(fields), route="planned"):
            cols: dict[str, Column] = {}
            for k in self.key_names:
                cols[k] = kv.data.column(k).take(last_take)
            for f in fields:
                col, spec = kv.data.column(f.name), self._agg_spec(f.name)
                on = field_on(spec, col)
                on_host += on == "host"
                by_scan += takes_scan(spec, col)
                with span("agg.field", field=f.name, fn=spec.function, on=on):
                    cols[f.name] = aggregate_merge(plan, col, spec, kv.kind)
            data = ColumnBatch(self.value_schema, cols)
            kind = np.full(len(last_take), int(RowKind.INSERT), dtype=np.uint8)
        _count_aggregate(kv.num_rows, len(last_take), len(fields), on_host, by_scan)
        return KVBatch(data, out_seq, kind)
