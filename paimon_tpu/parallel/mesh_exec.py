"""Mesh-sharded execution layer: real scans and compactions bucket-parallel
across the device mesh (ISSUE 7 tentpole).

`MeshExecutor` is the bridge the shard_map primitives in parallel/merge.py
were missing: table operations (merge read, compaction rewrite, writer flush)
dispatch their per-bucket merge jobs into it, and it executes a batch of
them in ONE shard_map call per merge-function family over the mesh's
"bucket" axis — the TPU-native mapping of the reference running one
Flink/Spark task per bucket (SURVEY §2.9, MergeTreeSplitGenerator.java:38).
A batch is a ROUND of the reader's plan (the next `feeder_lanes` splits in
split order, `MeshExecutor.round`), or, for submitters that have no rounds
to give (compaction, the writers), everything they have submitted so far.
Oversized buckets leave the bucket axis and range-shuffle over the "key"
axis instead (distributed_dedup_select: all_gather splitter sample +
all_to_all — the RangeShuffle.java analog), and sort-compact / dynamic-bucket
rescale use the same collective through `mesh_cluster_permutation` /
`range_partition_rows`.

This module is the one seam: it owns the active context (a ContextVar no
other module names). Table operations ask `maybe_mesh_exec(options)` for an
executor, dispatch code asks `current_mesh_context()` and gets the executor,
one of its rounds, or None, and a feeder thread installs either with
`MeshExecutor.active`. Three properties of the engine:

  GLOBAL LANE PLANNING — every job in a family batch shares ONE `LanePlan`
  computed from lane stats reduced across all shards
  (ops.lanes.plan_lanes_global). Per-shard plans can disagree on packed
  widths (a lane spanning 8 bits on shard A and 20 on shard B fuses
  differently), and packed operands from different plans are not comparable —
  fatal the moment values cross devices (range-shuffle splitters, stacked
  shard_map lanes). The parity suite pins a case where per-shard planning
  provably corrupts the distributed selection.

  HOST-SIDE FEEDER — the PR 4 SplitPipeline feeds the executor with one
  prefetch lane per device (table/read._mesh_batches, compact
  rewrite_dispatch), so IO + decode of round i+1 overlap the batched device
  merge of round i. Which jobs share a shard_map call is decided by the
  plan, never by how far the feeder threads happen to have come: resolving
  a job runs its round and no job of a later one.

  ONE DEVICE — gated behind `merge.engine = mesh` (default `single`); with
  a single visible device there is nothing to shard over and the existing
  single-device path runs, bit-identically.

Observability (docs/tracing.md): the mesh{buckets_sharded, shards, pad_rows,
exchange_rows, device_busy_ms, feeder_wait_ms} metric group; a bucket-axis
batch also counts merge{merges, rows_in, tiles, pad_rows, h2d_bytes,
d2h_bytes, winners} as ops/merge.py does (a job is a merge of one tile); the
spans mesh.plan, mesh.stack and, inside mesh.batch, mesh.h2d, mesh.run and
mesh.d2h. perfbench's per-layer readers read them on the cell
pk-8bucket-mesh.merge-read.
"""

from __future__ import annotations

import contextvars
import functools
import os
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass

import numpy as np

__all__ = [
    "MeshExecutor",
    "mesh_available",
    "resolve_merge_engine",
    "maybe_mesh_exec",
    "current_mesh_context",
    "distributed_dedup_select",
    "mesh_cluster_permutation",
    "mesh_feeder_lanes",
]


def _metrics():
    from ..metrics import mesh_metrics

    return mesh_metrics()


def mesh_available() -> bool:
    """True when the process can actually shard: >= 2 visible devices. One
    device runs the single-device path — the same kernels, nothing to shard
    over. A backend that fails to initialise raises here, like everywhere."""
    import jax

    return len(jax.devices()) >= 2


def resolve_merge_engine(options) -> str:
    """One resolution order everywhere: the PAIMON_TPU_MERGE_ENGINE env var
    (verify stages force both paths) beats the table's `merge.engine` option,
    which beats the default (`single`). Returns "mesh" or "single"; "mesh"
    runs the single-device path at the call sites when only one device is
    visible (mesh_available())."""
    env = os.environ.get("PAIMON_TPU_MERGE_ENGINE", "").strip().lower()
    if env in ("mesh", "single"):
        return env
    from ..options import CoreOptions

    v = (options.options.get(CoreOptions.MERGE_EXEC_ENGINE) or "single").lower()
    return "mesh" if v == "mesh" else "single"


_ACTIVE: contextvars.ContextVar["MeshExecutor | _Round | None"] = contextvars.ContextVar(
    "paimon_mesh_context", default=None
)


def current_mesh_context() -> "MeshExecutor | _Round | None":
    """The mesh context of this thread: the executor, the round of it that
    this dispatch belongs to, or None (single-device execution)."""
    return _ACTIVE.get()


@contextmanager
def maybe_mesh_exec(options):
    """The one mesh-entry seam for table operations: install a MeshExecutor
    as the active mesh context iff `merge.engine = mesh` resolves, the mesh
    is usable, and no context is already active (nesting would double-batch);
    yields None otherwise so callers keep their single-device path
    unchanged."""
    if (
        resolve_merge_engine(options) != "mesh"
        or current_mesh_context() is not None
        or not mesh_available()
    ):
        yield None
        return
    from ..options import CoreOptions

    mex = MeshExecutor(key_axis_rows=options.options.get(CoreOptions.PARALLEL_KEY_AXIS_ROWS))
    with mex.active():
        yield mex


@functools.lru_cache(maxsize=None)
def _meshes():
    """(bucket_mesh, key_mesh) over every visible device: all devices on the
    bucket axis for batched per-bucket jobs, all on the key axis for the
    range-shuffle path of one oversized bucket."""
    from .mesh import make_mesh

    bucket = make_mesh(None)  # {"bucket": N, "key": 1}
    key = make_mesh(None, bucket_parallel=1)  # {"bucket": 1, "key": N}
    return bucket, key


# one batched call is chunked so padded lanes stay under this many uint32s
_DEVICE_BUDGET_WORDS = 64 * 1024 * 1024


@dataclass
class _Job:
    kind: str  # "dedup" | "plan"
    lanes: np.ndarray  # (n, K) uint32 — RAW key lanes (planning is global)
    seq_lanes: np.ndarray | None  # (n, S) uint32
    compress: bool  # merge.lane-compression resolved by the submitter
    round: int | None = None  # the reader's round it was submitted in; None outside one


class _Round:
    """The executor as the dispatch of one split sees it (the mesh-context
    protocol of core.mergefn): a job submitted through it belongs to round
    `n` and runs with that round's jobs only."""

    def __init__(self, mex: "MeshExecutor", n: int):
        self.mex, self.n = mex, n

    @property
    def feeder_lanes(self) -> int:
        return self.mex.feeder_lanes

    def submit_dedup(self, lanes, seq_lanes, compress: bool = True) -> int:
        return self.mex._submit(_Job("dedup", lanes, seq_lanes, compress, self.n))

    def submit_plan(self, lanes, seq_lanes, compress: bool = True) -> int:
        return self.mex._submit(_Job("plan", lanes, seq_lanes, compress, self.n))

    def result(self, job_id: int):
        return self.mex.result(job_id)


class MeshExecutor:
    """Collects per-bucket merge jobs and executes them in family-batched
    shard_map calls over the bucket mesh. Implements the mesh-context
    protocol of core.mergefn (submit_dedup / submit_plan / result), so every
    dispatch/complete consumer (merge read, compaction, writer flush) routes
    through it unchanged. Submitters hand over RAW lanes — compression is
    decided here, once per family batch, from stats reduced over every shard
    (ops.lanes.plan_lanes_global)."""

    def __init__(self, mesh=None, key_axis_rows: int = 1 << 22):
        self.bucket_mesh, self.key_mesh = (mesh, mesh) if mesh is not None else _meshes()
        self.key_axis_rows = key_axis_rows
        self._jobs: dict[int, _Job] = {}
        self._results: dict[int, object] = {}
        self._next = 0
        self._lock = threading.Lock()
        self.executed_batches = 0  # observability: how many shard_map calls ran

    @property
    def feeder_lanes(self) -> int:
        """Host-side feeder width: one prefetch lane per device on the bucket
        axis (the SplitPipeline parallelism/depth the consumers use)."""
        return int(self.bucket_mesh.shape["bucket"])

    # ---- submission (thread-safe: feeder workers dispatch concurrently) ---
    def submit_dedup(self, lanes, seq_lanes, compress: bool = True) -> int:
        return self._submit(_Job("dedup", lanes, seq_lanes, compress))

    def submit_plan(self, lanes, seq_lanes, compress: bool = True) -> int:
        return self._submit(_Job("plan", lanes, seq_lanes, compress))

    def _submit(self, job: _Job) -> int:
        with self._lock:
            jid = self._next
            self._next += 1
            self._jobs[jid] = job
            return jid

    def round(self, n: int) -> _Round:
        """The context to install while round `n`'s splits dispatch. The
        rounds are the caller's plan (table/read._mesh_batches: the next
        `feeder_lanes` data splits in split order), fixed before any split
        dispatches; the caller resolves a round's jobs only once all of its
        dispatches are in."""
        return _Round(self, n)

    @contextmanager
    def active(self, round: int | None = None):
        """Install this executor, or its round `round`, as the mesh context
        of the calling thread for the extent of the block. A ContextVar does
        not cross into pipeline worker threads by itself: a feeder's dispatch
        wraps itself in this, or it would merge eagerly on the worker instead
        of enqueuing its job."""
        token = _ACTIVE.set(self if round is None else self.round(round))
        try:
            yield
        finally:
            _ACTIVE.reset(token)

    def result(self, job_id: int):
        if job_id not in self._results:
            with self._lock:
                job = self._jobs.get(job_id)
            self.execute(None if job is None else job.round)
        return self._results.pop(job_id)

    # ---- execution --------------------------------------------------------
    def execute(self, round: int | None = None) -> None:
        """Run the pending jobs of one round, or (None) all those submitted
        outside any round, which is what compaction and the writers mean.
        Jobs of other rounds, which the feeder may have submitted already,
        stay pending."""
        with self._lock:
            pending = {jid: job for jid, job in self._jobs.items() if job.round == round}
            for jid in pending:
                del self._jobs[jid]
        if not pending:
            return
        g = _metrics()
        g.counter("buckets_sharded").inc(len(pending))
        # family batches: one global plan and one shard_map program per
        # (family, lane arity, compression) group
        groups: dict[tuple, list[tuple[int, _Job]]] = {}
        huge: list[tuple[int, _Job]] = []
        p_key = self.key_mesh.shape.get("key", 1)
        for jid, job in pending.items():
            if (
                job.kind == "dedup"
                and p_key > 1
                and job.lanes.shape[0] >= self.key_axis_rows
            ):
                huge.append((jid, job))
            else:
                groups.setdefault(
                    (job.kind, job.lanes.shape[1], job.compress), []
                ).append((jid, job))
        for key, jobs in groups.items():
            kind, _, compress = key
            self._run_family(kind, jobs, compress)
        for jid, job in huge:
            # one hot bucket bigger than the key-axis threshold: leave the
            # bucket axis and range-shuffle its rows over the key axis
            self._results[jid] = self._run_key_axis(job)

    def _packed_lanes(self, jobs: list[tuple[int, _Job]], compress: bool):
        """Apply the ONE global plan to every job's lanes (or pass them
        through untouched when the compression layer is off — identity keeps
        the off-switch bit-exact)."""
        if not compress:
            return [j.lanes for _, j in jobs], None
        from ..metrics import span
        from ..ops.lanes import _record, apply_plan, plan_lanes_global

        rows = sum(j.lanes.shape[0] for _, j in jobs)
        with span("mesh.plan", jobs=len(jobs), rows=rows) as sp:
            plan = plan_lanes_global([j.lanes for _, j in jobs])
            packed = [apply_plan(plan, j.lanes) for _, j in jobs]
            sp.add(lanes_in=plan.lanes_in, lanes_out=plan.lanes_out)
        _record(plan, rows)
        return packed, plan

    def _run_family(self, kind: str, jobs: list[tuple[int, _Job]], compress: bool) -> None:
        from ..ops.merge import pad_size

        packed, _plan = self._packed_lanes(jobs, compress)
        axis = self.bucket_mesh.shape["bucket"]
        k_star = max(p.shape[1] for p in packed)
        s_star = max(
            (0 if j.seq_lanes is None else j.seq_lanes.shape[1]) for _, j in jobs
        )
        per_row_words = k_star + s_star + 1
        budget_rows = max(_DEVICE_BUDGET_WORDS // per_row_words, 1)
        # sort by padded size so similar-size jobs share a chunk (a chunk is
        # allocated at its max m; mixing one huge bucket with many tiny ones
        # would multiply the real footprint)
        order = sorted(range(len(jobs)), key=lambda i: jobs[i][1].lanes.shape[0])
        chunk: list[int] = []
        chunk_m = 0
        for i in order:
            m = pad_size(packed[i].shape[0])
            new_m = max(chunk_m, m)
            if chunk and (len(chunk) + 1) * new_m > budget_rows:
                self._run_chunk(kind, [(jobs[i2], packed[i2]) for i2 in chunk], axis, k_star, s_star)
                chunk, chunk_m = [], 0
                new_m = m
            chunk.append(i)
            chunk_m = new_m
        if chunk:
            self._run_chunk(kind, [(jobs[i2], packed[i2]) for i2 in chunk], axis, k_star, s_star)

    def _run_chunk(self, kind: str, items, axis: int, k: int, s: int) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from ..metrics import merge_metrics, span
        from ..ops.merge import MergePlan, _count_download, _count_kernel, _nbytes, pad_size

        from .merge import bucket_parallel_dedup_fn, bucket_parallel_plan_fn

        g = _metrics()
        m = max(pad_size(p.shape[0]) for _, p in items)
        # power-of-two multiples of the axis bound the jit cache to O(log n)
        # leading-dim shapes (same reasoning as ops/merge.pad_size)
        per_dev = -(-len(items) // axis)
        p2 = 1
        while p2 < per_dev:
            p2 <<= 1
        b = p2 * axis
        with span("mesh.stack", shards=b, rows=b * m):
            kl = np.full((b, m, k), 0xFFFFFFFF, dtype=np.uint32)
            sl = np.zeros((b, m, s), dtype=np.uint32)
            pad = np.ones((b, m), dtype=np.uint32)
            total_valid = 0
            for i, ((_, job), packed) in enumerate(items):
                n = packed.shape[0]
                total_valid += n
                kl[i, :n, : packed.shape[1]] = packed
                # missing lanes beyond a job's arity stay constant — constant
                # lanes affect neither ordering nor segmentation
                kl[i, :n, packed.shape[1] :] = 0
                if job.seq_lanes is not None and job.seq_lanes.shape[1]:
                    sl[i, :n, : job.seq_lanes.shape[1]] = job.seq_lanes
                pad[i, :n] = 0
        g.counter("shards").inc()
        g.counter("pad_rows").inc(b * m - total_valid)
        merge_metrics().counter("merges").inc(len(items))
        self.executed_batches += 1
        fn = (bucket_parallel_dedup_fn if kind == "dedup" else bucket_parallel_plan_fn)(self.bucket_mesh, k, s)
        with span("mesh.batch", histogram=g.histogram("device_busy_ms"), shards=b, pad_rows=b * m - total_valid):
            # no step below waits for the one before it: the upload and the
            # call are enqueued, and the download blocks until the program is
            # done. So mesh.d2h is the wait for the device and the copy back.
            with span("mesh.h2d"):
                operands = (kl, sl, pad)
                _count_kernel(operands, total_valid, b * m, tiles=len(items))
                operands = jax.device_put(operands, NamedSharding(self.bucket_mesh, PartitionSpec("bucket")))
            with span("mesh.run"):
                outs = fn(*operands)
            with span("mesh.d2h"):
                outs = [np.asarray(o) for o in outs]
                winners = int(outs[1].sum()) if kind == "dedup" else 0  # pad shards select nothing
                _count_download(_nbytes(outs), winners)
        if kind == "dedup":
            packed_out, counts = outs
            for i, ((jid, _), _p) in enumerate(items):
                self._results[jid] = packed_out[i, : int(counts[i])]
        else:
            perm, seg_start, keep_last, seg_id = outs
            for i, ((jid, job), _p) in enumerate(items):
                self._results[jid] = MergePlan(
                    perm=perm[i],
                    seg_start=seg_start[i],
                    keep_last=keep_last[i],
                    seg_id=seg_id[i],
                    n=job.lanes.shape[0],
                    m=m,
                )

    def _run_key_axis(self, job: _Job) -> np.ndarray:
        """One oversized bucket's dedup range-shuffled over the key axis.
        The global-plan rule matters most here: every device packs its row
        range with the SAME plan, so the all_gather'd splitter sample and the
        exchanged lanes stay comparable."""
        from ..metrics import timed

        g = _metrics()
        lanes = job.lanes
        if job.compress:
            from ..ops.lanes import _record, apply_plan, plan_lanes_global

            plan = plan_lanes_global([lanes])
            lanes = apply_plan(plan, lanes)
            _record(plan, lanes.shape[0])
        g.counter("shards").inc()
        g.counter("exchange_rows").inc(lanes.shape[0])
        self.executed_batches += 1
        if lanes.shape[1] == 0:
            # globally constant key: one winner, no device trip
            from ..ops.lanes import scalar_dedup_winner

            return scalar_dedup_winner(job.seq_lanes, lanes.shape[0])
        with timed(g.histogram("device_busy_ms")):
            return distributed_dedup_select(self.key_mesh, lanes, job.seq_lanes)


# ---------------------------------------------------------------------------
# key-axis path: one oversized bucket range-partitioned over all devices
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _key_axis_dedup_fn(mesh, k: int, s: int):
    """jitted range-shuffle dedup over the mesh's key axis (cached per
    (mesh, lane arity) like the bucket-axis programs of parallel/merge.py)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
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
    out = np.asarray(_key_axis_dedup_fn(mesh, k, s)(kl, sl, pad))
    # shards own ascending key ranges and emit sorted order -> already key order
    return out[out != np.uint32(0xFFFFFFFF)].astype(np.int32)


def mesh_feeder_lanes(options) -> int:
    """Feeder width for mesh-driven host pipelines outside an installed
    executor (sort-compact's bucket loop): one lane per device on the bucket
    axis, or 0 when the mesh engine is off/unusable (callers keep their
    serial loop)."""
    if resolve_merge_engine(options) != "mesh" or not mesh_available():
        return 0
    return int(_meshes()[0].shape["bucket"])


# ---------------------------------------------------------------------------
# cross-bucket repartition: sort-compact clustering / dynamic-bucket rescale
# ---------------------------------------------------------------------------


def mesh_cluster_permutation(lanes: np.ndarray, options) -> np.ndarray | None:
    """Distributed clustering sort for sort-compact (and the row-repartition
    primitive a dynamic-bucket rescale uses): rows range-shuffled over the
    mesh's key axis, each device sorting its key range locally, the global
    permutation recovered from the row-id lane that rides the exchange.
    Returns the STABLE sort permutation — bit-identical to the single-device
    `merge_plan(...)` path — or None when the mesh engine is off, the mesh is
    unusable, or the batch is below `parallel.key-axis.rows` (collective
    latency would beat the win on small batches)."""
    from ..options import CoreOptions

    if resolve_merge_engine(options) != "mesh" or not mesh_available():
        return None
    n = lanes.shape[0]
    threshold = options.options.get(CoreOptions.PARALLEL_KEY_AXIS_ROWS)
    if n < max(int(threshold), 2):
        return None
    from ..ops.lanes import apply_plan, plan_lanes_global
    from .merge import range_partition_rows

    key_mesh = _meshes()[1]
    p = key_mesh.shape["key"]
    if p < 2 or n < p:
        return None
    compress = options.lane_compression
    if compress:
        packed = apply_plan(plan_lanes_global([lanes]), lanes)
    else:
        packed = np.ascontiguousarray(lanes, dtype=np.uint32)
    if packed.shape[1] == 0:
        # every row carries the same curve code: the stable sort is the
        # identity permutation
        return np.arange(n, dtype=np.int64)
    from ..ops.merge import pad_size

    # power-of-two per-device shards bound the jit cache to O(log n) shapes
    # (same reasoning as ops/merge.pad_size)
    m_loc = pad_size(-(-n // p))
    total = m_loc * p
    kl = np.full((total, packed.shape[1]), 0xFFFFFFFF, dtype=np.uint32)
    kl[:n] = packed
    rid = np.arange(total, dtype=np.uint32)
    pad = np.zeros(total, dtype=np.uint32)
    pad[n:] = 1
    g = _metrics()
    g.counter("shards").inc()
    g.counter("exchange_rows").inc(n)
    g.counter("pad_rows").inc(total - n)
    t0 = time.perf_counter()
    rows_sorted, pad_sorted = range_partition_rows(key_mesh, kl, rid, pad)
    out = rows_sorted[pad_sorted == 0].astype(np.int64)
    g.histogram("device_busy_ms").update((time.perf_counter() - t0) * 1000)
    return out
