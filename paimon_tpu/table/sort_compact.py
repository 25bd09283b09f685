"""Sort-compaction: rewrite a table clustered by a space-filling curve.

Parity: the reference's SortCompactAction + TableSorter (flink/sorter/:
ZorderSorter, HilbertSorter, order) over RangeShuffle — here the single-host
path sorts the whole table through the device sort kernel; the distributed
path is paimon_tpu.parallel.range_partition_lanes over the "key" mesh axis.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Sequence

import numpy as np

from ..core.kv import KVBatch
from ..core.manifest import CommitMessage, ManifestCommittable
from ..data.keys import encode_key_lanes, exact_string_pool
from ..ops.merge import merge_plan
from ..options import CoreOptions
from ..ops.zorder import hilbert_lanes, z_order_lanes
from ..types import TypeRoot

if TYPE_CHECKING:
    from . import FileStoreTable

__all__ = ["sort_compact"]


def sort_compact(
    table: "FileStoreTable",
    columns: Sequence[str],
    order: str = "zorder",
    commit_identifier: int | None = None,
) -> int:
    """Rewrites every bucket clustered by `columns` under the given curve
    (zorder | hilbert | order). Returns rows rewritten. Append tables only —
    PK tables are already key-clustered by the LSM."""
    if table.is_primary_key_table:
        raise ValueError("sort-compact applies to append-only tables (PK tables are key-clustered)")
    if order not in ("zorder", "hilbert", "order"):
        raise ValueError(f"unknown sort order {order!r}")
    store = table.store
    plan = store.new_scan().plan()
    messages: list[CommitMessage] = []
    total = 0
    from ..options import SortEngine

    # CPU-only backend: clustering is a plain stable sort of the curve
    # codes — the host lexsort wins (same adaptive rule as merge reads,
    # mergefn.effective_sort_engine); resolved once for the whole call
    use_host_sort = store.merge_executor().effective_sort_engine() == SortEngine.NUMPY
    jobs = [
        (partition, bucket, files)
        for partition, buckets in plan.grouped().items()
        for bucket, files in buckets.items()
    ]

    def read_job(job):
        partition, bucket, files = job
        rf = store.reader_factory(partition, bucket)
        ordered = sorted(files, key=lambda f: (f.min_sequence_number, f.file_name))
        from ..parallel.pipeline import bounded_map

        return KVBatch.concat(bounded_map(rf.read, ordered))

    # merge.engine = mesh: buckets stream through the host-side feeder (one
    # prefetch lane per device) so bucket i+1's reads overlap bucket i's
    # clustering sort; the per-bucket processing below is unchanged, so the
    # rewritten files are bit-identical to the serial loop
    from ..parallel.mesh_exec import mesh_feeder_lanes

    lanes_n = mesh_feeder_lanes(store.options)
    if lanes_n > 1 and len(jobs) > 1:
        from ..parallel.pipeline import SplitPipeline

        kv_iter = SplitPipeline(parallelism=lanes_n, depth=lanes_n, stage="compact").map_ordered(
            jobs, read_job
        )
    else:
        kv_iter = (read_job(j) for j in jobs)
    for (partition, bucket, files), kv in zip(jobs, kv_iter):
        if kv.num_rows == 0:
            continue
        var_roots = (TypeRoot.CHAR, TypeRoot.VARCHAR, TypeRoot.BINARY, TypeRoot.VARBINARY)
        # exact pools (code-domain aware): len(pools[c]) must equal the
        # expanded build's so the zorder spread factor — and therefore the
        # clustering permutation — is identical with merge.dict-domain on
        pools = {
            c: exact_string_pool([kv.data.column(c)])
            for c in columns
            if kv.data.schema.field(c).type.root in var_roots
        }
        lanes = encode_key_lanes(kv.data, columns, pools)
        # zorder.var-length-contribution: how many BYTES a var-length
        # column contributes to the interleave (reference ZIndexer
        # varTypeSize). Ranks are dense; spread them over the full 32-bit
        # lane, then keep the top contribution*8 bits — fewer bits =
        # coarser clustering for that column.
        contrib = int(store.options.options.get(CoreOptions.ZORDER_VAR_LENGTH_CONTRIBUTION))
        if order in ("zorder", "hilbert") and contrib < 4:
            keep_bits = max(1, contrib * 8)
            for ci, c in enumerate(columns):
                if kv.data.schema.field(c).type.root in var_roots and len(pools.get(c, ())):
                    scale = np.uint64(0x100000000) // np.uint64(max(len(pools[c]), 1))
                    spread = (lanes[:, ci].astype(np.uint64) * scale).astype(np.uint32)
                    lanes[:, ci] = spread & np.uint32(~np.uint32((1 << (32 - keep_bits)) - 1))
        if order == "zorder":
            lanes = z_order_lanes(lanes)
        elif order == "hilbert":
            lanes = hilbert_lanes(lanes)
        # key-lane compression (ops/lanes.py): curve code lanes truncate
        # and pack like any key — identical clustering permutation
        # (order- and stability-preserving), fewer sort operands
        compress = store.options.lane_compression
        perm = None
        if not use_host_sort:
            # merge.engine = mesh: the clustering sort range-shuffles
            # rows over the mesh's key axis (range_partition_rows — the
            # RangeShuffle.java analog) and recovers the same stable
            # permutation; None below the key-axis threshold / off mesh
            from ..parallel.mesh_exec import mesh_cluster_permutation

            perm = mesh_cluster_permutation(lanes, store.options)
        if perm is None and use_host_sort:
            from ..data.keys import lexsort_rows
            from ..ops.lanes import compress_key_lanes

            sort_lanes, _plan = compress_key_lanes(lanes, compress, enable_ovc=False)
            perm = lexsort_rows(sort_lanes)
        elif perm is None:
            # device sort; stability keeps arrival order on ties
            p = merge_plan(lanes, compress=compress)
            perm = p.perm[p.valid_sorted]
        sorted_kv = kv.take(perm)
        wf = store.writer_factory(partition, bucket)
        # sort-compaction.range-strategy=size: roll output files by
        # MEASURED bytes (var-width skew packs evenly); quantity keeps
        # the schema estimate (row-count driven)
        measured = None
        if store.options.options.get(CoreOptions.SORT_COMPACTION_RANGE_STRATEGY).lower() == "size":
            total_bytes = 0.0
            n_rows = sorted_kv.num_rows
            for col in sorted_kv.data.columns.values():
                if col.values.dtype == np.dtype(object):
                    sample = col.values[: min(n_rows, 4096)]
                    # float scaling: integer floor undercounts up to 2x
                    total_bytes += sum(len(str(v)) for v in sample) * (n_rows / max(len(sample), 1))
                else:
                    total_bytes += col.values.nbytes
            measured = total_bytes / max(n_rows, 1)
        after = wf.write(sorted_kv, level=0, file_source="compact", measured_row_bytes=measured)
        messages.append(
            CommitMessage(
                partition,
                bucket,
                max(store.options.bucket, 1),
                compact_before=list(files),
                compact_after=after,
            )
        )
        total += kv.num_rows
    if messages:
        ident = commit_identifier if commit_identifier is not None else (1 << 63) - 3
        store.new_commit().commit(ManifestCommittable(ident, messages=messages))
    return total
