"""Mesh execution of table work: the bridge from Table operations to the
distributed kernels.

The reference distributes table work by running one task per (partition,
bucket) on a Flink/Spark cluster (FlinkSinkBuilder.java:223 topology,
MergeTreeSplitGenerator.java:38 split generation). The TPU-native mapping
implemented here: table operations (write flush, compaction rewrite,
merge-read) run in two phases — a *dispatch* phase that reads inputs and
submits per-bucket merge jobs, and a *complete* phase that consumes results —
and a `MeshBatchContext` collects every job dispatched in between and executes
them all in ONE shard_map over the mesh's "bucket" axis (buckets are
key-disjoint: pure data parallelism, zero collectives). Oversized buckets are
instead range-partitioned over the "key" axis (all_gather splitter sample +
all_to_all shuffle + local merge — the RangeShuffle.java analog), so a single
hot bucket scales past one device too.

Commit stays host-side: in multi-process runs only the process-0 coordinator
commits (distributed.is_commit_coordinator), exactly like the reference's
single-parallelism committer operator.
"""

from __future__ import annotations

import contextvars
import functools
from contextlib import contextmanager
from dataclasses import dataclass, field

import numpy as np
from jax import shard_map

__all__ = [
    "MeshBatchContext",
    "mesh_batch",
    "maybe_mesh_batch",
    "current_mesh_context",
    "distributed_dedup_select",
]

_ACTIVE: contextvars.ContextVar["MeshBatchContext | None"] = contextvars.ContextVar(
    "paimon_mesh_batch", default=None
)

# one batched call is chunked so padded lanes stay under this many uint32s
_DEVICE_BUDGET_WORDS = 64 * 1024 * 1024


def current_mesh_context() -> "MeshBatchContext | None":
    return _ACTIVE.get()


@contextmanager
def mesh_batch(mesh=None, key_axis_rows: int = 1 << 22):
    """Install a MeshBatchContext for the dynamic extent. Dispatch-phase
    merge_async calls enqueue jobs; the first result() executes everything
    pending in one batched mesh call."""
    ctx = MeshBatchContext(mesh, key_axis_rows=key_axis_rows)
    token = _ACTIVE.set(ctx)
    try:
        yield ctx
    finally:
        _ACTIVE.reset(token)


@contextmanager
def maybe_mesh_batch(store):
    """The one mesh-entry seam for table operations. `merge.engine = mesh`
    (the ISSUE 7 executor: family-batched shard_maps, global lane plans,
    per-device feeder — parallel.mesh_exec) takes precedence; otherwise the
    legacy parallel.mesh.enabled batching context; no-op when neither is on,
    a context is already active, or <2 devices are visible (cpu fallback)."""
    from ..options import CoreOptions

    from .mesh_exec import maybe_mesh_exec, resolve_merge_engine

    if resolve_merge_engine(store.options) == "mesh" and current_mesh_context() is None:
        with maybe_mesh_exec(store.options) as ctx:
            yield ctx
        return
    enabled = store.options.options.get(CoreOptions.PARALLEL_MESH_ENABLED)
    if not enabled or current_mesh_context() is not None:
        yield None
        return
    import jax

    if len(jax.devices()) < 2:
        yield None
        return
    threshold = store.options.options.get(CoreOptions.PARALLEL_KEY_AXIS_ROWS)
    with mesh_batch(key_axis_rows=threshold) as ctx:
        yield ctx


# ---------------------------------------------------------------------------
# batched kernels (bucket axis)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _meshes():
    """(bucket_mesh, key_mesh) over every visible device: all devices on the
    bucket axis for batched per-bucket jobs, all on the key axis for the
    range-shuffle path of one oversized bucket."""
    from .mesh import make_mesh

    bucket = make_mesh(None)  # {"bucket": N, "key": 1}
    key = make_mesh(None, bucket_parallel=1)  # {"bucket": 1, "key": N}
    return bucket, key


class _KernelCache:
    """jit+shard_map programs keyed by (kind, lane arities); the mesh is fixed
    per process so one cache serves every context."""

    def __init__(self):
        self._fns: dict = {}

    def batched_dedup(self, mesh, k: int, s: int):
        key = ("dedup", id(mesh), k, s)
        fn = self._fns.get(key)
        if fn is None:
            fn = _make_batched_dedup(mesh, k, s)
            self._fns[key] = fn
        return fn

    def batched_plan(self, mesh, k: int, s: int):
        key = ("plan", id(mesh), k, s)
        fn = self._fns.get(key)
        if fn is None:
            fn = _make_batched_plan(mesh, k, s)
            self._fns[key] = fn
        return fn

    def key_axis_dedup(self, mesh, k: int, s: int):
        key = ("keyaxis", id(mesh), k, s)
        fn = self._fns.get(key)
        if fn is None:
            fn = _make_key_axis_dedup(mesh, k, s)
            self._fns[key] = fn
        return fn


_KERNELS = _KernelCache()


def _make_batched_dedup(mesh, k: int, s: int):
    """(B, m, K) uint32 key lanes, (B, m, S) seq lanes, (B, m) pad ->
    per-bucket packed selected input indices + counts, buckets sharded over
    the mesh's bucket axis. The kernel body IS ops.merge.sorted_segments /
    pack_selected — one copy of the semantics for mesh and single-device."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..ops.merge import pack_selected, sorted_segments

    def per_bucket(kl, sl, pf):  # (m, K), (m, S), (m,)
        pad_sorted, perm, _, keep_last, _ = sorted_segments(k, s, kl.T, sl.T, pf)
        return pack_selected(keep_last & (pad_sorted == 0), perm)

    def shard_fn(kl, sl, pf):
        return jax.vmap(per_bucket)(kl, sl, pf)

    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P("bucket", None, None), P("bucket", None, None), P("bucket", None)),
        out_specs=(P("bucket", None), P("bucket")),
    )
    return jax.jit(fn)


def _make_batched_plan(mesh, k: int, s: int):
    """Like _make_batched_dedup but returns the full merge plan arrays
    (perm, seg_start, keep_last, seg_id) per bucket — the non-dedup engines
    continue host-side with segment reductions."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..ops.merge import sorted_segments

    def per_bucket(kl, sl, pf):
        _, perm, seg_start, keep_last, seg_id = sorted_segments(k, s, kl.T, sl.T, pf)
        return perm, seg_start, keep_last, seg_id

    def shard_fn(kl, sl, pf):
        return jax.vmap(per_bucket)(kl, sl, pf)

    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P("bucket", None, None), P("bucket", None, None), P("bucket", None)),
        out_specs=(P("bucket", None), P("bucket", None), P("bucket", None), P("bucket", None)),
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# key-axis path: one oversized bucket range-partitioned over all devices
# ---------------------------------------------------------------------------


def _make_key_axis_dedup(mesh, k: int, s: int):
    """jitted range-shuffle dedup over the mesh's key axis (cached per
    (mesh, lane arity) like the bucket-axis kernels)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from .merge import _local_plan, _range_exchange

    p = mesh.shape["key"]
    sentinel = np.uint32(0xFFFFFFFF)

    def shard_fn(klx, slx, pfx):
        rk, rs, rp = _range_exchange(klx.T, slx.T, pfx, "key", p, k, s + 1)
        perm, _, keep_last, _ = _local_plan(k, s + 1, rk, rs, rp)
        sel = keep_last & (rp[perm] == 0)
        rowids = rs[s][perm]
        return jnp.where(sel, rowids, sentinel)

    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P("key", None), P("key", None), P("key")),
        out_specs=P("key"),
    )
    return jax.jit(fn)


def distributed_dedup_select(mesh, key_lanes: np.ndarray, seq_lanes: np.ndarray | None = None) -> np.ndarray:
    """Dedup selection for ONE bucket whose rows are sharded over the mesh's
    "key" axis: sample splitters (all_gather), range-shuffle rows to their
    owner (all_to_all over ICI), locally sort + keep-last, return the winning
    INPUT row indices in global key order. The row id rides the shuffle as the
    final sort lane, which reproduces input-order tie-break across devices."""
    n, k = key_lanes.shape
    p = mesh.shape["key"]
    if seq_lanes is None:
        seq_lanes = np.zeros((n, 0), dtype=np.uint32)
    s = seq_lanes.shape[1]
    m_loc = -(-n // p)  # ceil
    total = m_loc * p
    kl = np.full((total, k), 0xFFFFFFFF, dtype=np.uint32)
    kl[:n] = key_lanes
    sl = np.zeros((total, s + 1), dtype=np.uint32)
    sl[:n, :s] = seq_lanes
    sl[:, s] = np.arange(total, dtype=np.uint32)  # row id = last tie-break lane
    pad = np.zeros(total, dtype=np.uint32)
    pad[n:] = 1
    out = np.asarray(_KERNELS.key_axis_dedup(mesh, k, s)(kl, sl, pad))
    # shards own ascending key ranges and emit sorted order -> already key order
    return out[out != np.uint32(0xFFFFFFFF)].astype(np.int32)


# ---------------------------------------------------------------------------
# the batch context
# ---------------------------------------------------------------------------


@dataclass
class _Job:
    kind: str  # "dedup" | "plan"
    lanes: np.ndarray  # (n, K) uint32
    seq_lanes: np.ndarray | None  # (n, S) uint32


@dataclass
class MeshBatchContext:
    """Collects merge jobs dispatched by table operations and executes them
    in batched mesh calls. Results are MergePlan objects for "plan" jobs and
    selected input-index arrays for "dedup" jobs."""

    mesh: object = None
    key_axis_rows: int = 1 << 22
    _jobs: dict[int, _Job] = field(default_factory=dict)
    _results: dict[int, object] = field(default_factory=dict)
    _next: int = 0
    executed_batches: int = 0  # observability: how many mesh calls ran

    def submit_dedup(self, lanes: np.ndarray, seq_lanes: np.ndarray | None) -> int:
        return self._submit(_Job("dedup", lanes, seq_lanes))

    def submit_plan(self, lanes: np.ndarray, seq_lanes: np.ndarray | None) -> int:
        return self._submit(_Job("plan", lanes, seq_lanes))

    def _submit(self, job: _Job) -> int:
        jid = self._next
        self._next += 1
        self._jobs[jid] = job
        return jid

    def result(self, job_id: int):
        if job_id not in self._results:
            self.execute()
        return self._results.pop(job_id)

    # ---- execution -----------------------------------------------------
    def execute(self) -> None:
        if not self._jobs:
            return
        bucket_mesh, key_mesh = (self.mesh, self.mesh) if self.mesh is not None else _meshes()
        pending = self._jobs
        self._jobs = {}
        huge: list[tuple[int, _Job]] = []
        by_kind: dict[str, list[tuple[int, _Job]]] = {"dedup": [], "plan": []}
        p_key = key_mesh.shape.get("key", 1)
        for jid, job in pending.items():
            if job.kind == "dedup" and p_key > 1 and job.lanes.shape[0] >= self.key_axis_rows:
                huge.append((jid, job))
            else:
                by_kind[job.kind].append((jid, job))
        for jid, job in huge:
            self._results[jid] = distributed_dedup_select(key_mesh, job.lanes, job.seq_lanes)
            self.executed_batches += 1
        for kind, jobs in by_kind.items():
            if jobs:
                self._execute_bucket_batch(bucket_mesh, kind, jobs)

    def _execute_bucket_batch(self, mesh, kind: str, jobs: list[tuple[int, _Job]]) -> None:
        from ..ops.merge import pad_size

        axis = mesh.shape["bucket"]
        k_star = max(j.lanes.shape[1] for _, j in jobs)
        k_star = max(k_star, 1)
        s_star = max((0 if j.seq_lanes is None else j.seq_lanes.shape[1]) for _, j in jobs)
        per_row_words = k_star + s_star + 1
        budget_rows = max(_DEVICE_BUDGET_WORDS // per_row_words, 1)
        # sort by padded size so similar-size jobs share a chunk: every job in
        # a chunk is allocated at the chunk MAX m, so mixing one huge bucket
        # with many tiny ones would multiply the real footprint (and inflate
        # the tiny jobs' MergePlan.m downstream)
        jobs = sorted(jobs, key=lambda item: item[1].lanes.shape[0])
        chunk: list[tuple[int, _Job]] = []
        chunk_m = 0
        for item in jobs:
            m = pad_size(item[1].lanes.shape[0])
            new_m = max(chunk_m, m)
            if chunk and (len(chunk) + 1) * new_m > budget_rows:
                self._run_chunk(mesh, kind, chunk, axis, k_star, s_star)
                chunk, chunk_m = [], 0
                new_m = m
            chunk.append(item)
            chunk_m = new_m
        if chunk:
            self._run_chunk(mesh, kind, chunk, axis, k_star, s_star)

    def _run_chunk(self, mesh, kind: str, jobs, axis: int, k: int, s: int) -> None:
        from ..ops.merge import MergePlan, pad_size

        m = max(pad_size(j.lanes.shape[0]) for _, j in jobs)
        # power-of-two multiples of the axis bound the jit cache to
        # O(log n) leading-dim shapes (same reasoning as ops/merge.pad_size)
        per_dev = -(-len(jobs) // axis)
        p2 = 1
        while p2 < per_dev:
            p2 <<= 1
        b = p2 * axis
        kl = np.full((b, m, k), 0xFFFFFFFF, dtype=np.uint32)
        sl = np.zeros((b, m, s), dtype=np.uint32)
        pad = np.ones((b, m), dtype=np.uint32)
        for i, (_, job) in enumerate(jobs):
            n = job.lanes.shape[0]
            kl[i, :n, : job.lanes.shape[1]] = job.lanes
            # missing lanes beyond a job's arity stay constant 0xFF.. / 0 —
            # constant lanes affect neither ordering nor segmentation
            kl[i, :n, job.lanes.shape[1] :] = 0
            if job.seq_lanes is not None and job.seq_lanes.shape[1]:
                sl[i, :n, : job.seq_lanes.shape[1]] = job.seq_lanes
            pad[i, :n] = 0
        self.executed_batches += 1
        if kind == "dedup":
            packed, counts = _KERNELS.batched_dedup(mesh, k, s)(kl, sl, pad)
            packed = np.asarray(packed)
            counts = np.asarray(counts)
            for i, (jid, _) in enumerate(jobs):
                self._results[jid] = packed[i, : int(counts[i])]
        else:
            perm, seg_start, keep_last, seg_id = map(
                np.asarray, _KERNELS.batched_plan(mesh, k, s)(kl, sl, pad)
            )
            for i, (jid, job) in enumerate(jobs):
                self._results[jid] = MergePlan(
                    perm=perm[i],
                    seg_start=seg_start[i],
                    keep_last=keep_last[i],
                    seg_id=seg_id[i],
                    n=job.lanes.shape[0],
                    m=m,
                )
