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

from ..data.batch import Column, ColumnBatch
from ..data.keys import build_string_pool, encode_key_lanes, split_int64_lanes
from ..metrics import read_metrics, span
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
from ..ops.aggregates import _gather_column
from ..types import RowKind, RowType, TypeRoot
from .kv import KVBatch

__all__ = ["MergeExecutor"]


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
        wins unconditionally (a table that explicitly chose numpy/pallas
        keeps it); then the PAIMON_TPU_SORT_ENGINE env var (the CI forcing
        knob, pattern of PAIMON_TPU_MERGE_ENGINE) pins every table that
        did not choose; otherwise the default ADAPTS to the resolved
        platform: the host lexsort path on a CPU-only backend (a single
        stable `np.lexsort` beats XLA:CPU's variadic stable sort ~3x at the
        1M-row scale), the device kernel everywhere else. The check never
        initializes a backend (ops.merge.resolved_platform_is_cpu).
        PAIMON_TPU_FORCE_DEVICE_ENGINE=1 pins the device kernel so the test
        suite exercises the dispatch path on its virtual-CPU mesh."""
        import os

        from ..options import CoreOptions, SortEngine

        if self.options.options.contains(CoreOptions.SORT_ENGINE):
            return SortEngine(self.options.sort_engine)
        env = os.environ.get("PAIMON_TPU_SORT_ENGINE", "").strip().lower()
        if env:
            return SortEngine(env)
        if os.environ.get("PAIMON_TPU_FORCE_DEVICE_ENGINE", "") == "1":
            return SortEngine(self.options.sort_engine)
        from ..ops.merge import resolved_platform_is_cpu

        if resolved_platform_is_cpu():
            return SortEngine.NUMPY
        return SortEngine(self.options.sort_engine)

    def _engine_str(self) -> str:
        """The ops-layer engine tag for the sorted_segments seam: 'pallas'
        routes every merge kernel's sort+boundary preamble through the fused
        pallas kernels; everything else is the stock XLA path. (The numpy
        engine never reaches a device kernel — callers branch before.)"""
        from ..options import SortEngine

        return "pallas" if self.effective_sort_engine() == SortEngine.PALLAS else "xla"

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
        return merge_plan(lanes, seq_lanes, compress=self._compress, engine=self._engine_str())

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
                return ("sync", kv)
            seq_lanes = self._seq_lanes(kv, seq_ascending)
            if engine == SortEngine.NUMPY:
                return ("sync", kv.take(_numpy_dedup_select(lanes, seq_lanes, self._compress, plan)))
            if ctx is not None:
                # submit RAW lanes — compression is decided ONCE per family
                # batch from stats reduced over every shard
                # (ops.lanes.plan_lanes_global), so all shards of one
                # shard_map agree on packed widths (ISSUE 7 fix)
                from ..ops.lanes import resolve_compress

                return (
                    "dedup",
                    ctx,
                    ctx.submit_dedup(lanes, seq_lanes, compress=resolve_compress(self._compress)),
                    kv,
                )
            backend = "pallas" if engine == SortEngine.PALLAS else "xla"
            from ..ops.merge import deduplicate_resolve, deduplicate_select_async

            take = deduplicate_resolve(
                deduplicate_select_async(lanes, seq_lanes, backend=backend, compress=self._compress, plan=plan)
            )
            return ("sync", self.gather(kv, take))
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
        return (
            "sync",
            self._merge_with_plan(
                kv, merge_plan(lanes, seq_lanes, compress=self._compress, engine=self._engine_str())
            ),
        )

    def merge_resolve(self, handle) -> KVBatch:
        tag = handle[0]
        if tag == "sync":
            return handle[1]
        _, ctx, job_id, kv = handle
        if tag == "dedup":
            return self.gather(kv, ctx.result(job_id))
        return self._merge_with_plan(kv, ctx.result(job_id))

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
        backend = "pallas" if engine == SortEngine.PALLAS else "xla"
        if seq_lanes is None and run_offsets is not None:
            tile_rows = self.options.options.get(CoreOptions.MERGE_READ_BATCH_ROWS)
            # the tiled dispatcher owns the compression seam (one plan per
            # merge, shared by every tile) and the all-constant fast path
            return (
                "tiled",
                deduplicate_tiled_dispatch(
                    lanes, run_offsets, tile_rows, backend=backend, compress=self._compress, plan=plan
                ),
            )
        return (
            "single",
            deduplicate_select_async(lanes, seq_lanes, backend=backend, compress=self._compress, plan=plan),
        )

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
        returns per-field sources + existence + winners in one device trip."""
        from ..ops.merge import fused_partial_update

        remove_on_delete = self.options.options.get(CoreOptions.PARTIAL_UPDATE_REMOVE_RECORD_ON_DELETE)
        self._check_partial_update_deletes(kv, remove_on_delete)
        fields = [f for f in self.value_schema.fields if f.name not in self.key_names]
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
            engine=self._engine_str(),
        )
        cols: dict[str, Column] = {}
        for k in self.key_names:
            cols[k] = kv.data.column(k).take(last_take)
        for fi, f in enumerate(fields):
            cols[f.name] = _gather_column(kv.data.column(f.name), src[fi])
        data = ColumnBatch(self.value_schema, cols)
        # without remove-on-delete every row is +I/+U (checked above), so
        # every segment exists; with it, vanished keys stay as -D rows
        kind = np.where(exists, int(RowKind.INSERT), int(RowKind.DELETE)).astype(np.uint8)
        return KVBatch(data, kv.seq.take(last_take), kind)

    def _aggregate_fused(self, kv: KVBatch, lanes, seq_lanes, fields, specs, cols_in) -> KVBatch:
        """Single-call aggregation: every column's segment reduction runs in
        the same kernel as the sort."""
        from ..ops.aggregates import fused_aggregate

        agg_cols, last_take = fused_aggregate(
            lanes, seq_lanes, cols_in, specs, kv.kind, compress=self._compress, engine=self._engine_str()
        )
        cols: dict[str, Column] = {}
        for k in self.key_names:
            cols[k] = kv.data.column(k).take(last_take)
        for f, c in zip(fields, agg_cols):
            cols[f.name] = c
        data = ColumnBatch(self.value_schema, cols)
        kind = np.full(len(last_take), int(RowKind.INSERT), dtype=np.uint8)
        return KVBatch(data, kv.seq.take(last_take), kind)

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
        return out

    def _group_take(self, kv: KVBatch, seq_col: str, fields: Sequence[str]) -> dict[str, Column]:
        from ..ops.aggregates import _pick_fn
        from ..ops.merge import pad_to

        import jax.numpy as jnp

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
        gplan = merge_plan(key_lanes, seq_lanes, compress=self._compress, engine=self._engine_str())
        candidate = g_valid & np.isin(kv.kind, (int(RowKind.INSERT), int(RowKind.UPDATE_AFTER)))
        src = _pick_fn(True)(
            jnp.asarray(gplan.perm), jnp.asarray(gplan.seg_id), jnp.asarray(pad_to(candidate, gplan.m, False))
        )
        src = np.asarray(src)[: gplan.num_segments]
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
        cols: dict[str, Column] = {}
        for k in self.key_names:
            cols[k] = kv.data.column(k).take(last_take)
        for f in self.value_schema.fields:
            if f.name in self.key_names:
                continue
            cols[f.name] = aggregate_merge(plan, kv.data.column(f.name), self._agg_spec(f.name), kv.kind)
        data = ColumnBatch(self.value_schema, cols)
        kind = np.full(len(last_take), int(RowKind.INSERT), dtype=np.uint8)
        return KVBatch(data, out_seq, kind)
