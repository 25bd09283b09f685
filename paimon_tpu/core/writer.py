"""The merge-tree writer: memtable, flush-through-merge, compaction hooks.

Parity: /root/reference/paimon-core/.../mergetree/MergeTreeWriter.java:57 —
assigns sequence numbers (:164), buffers into a sort buffer, flushes the
buffer through the merge function into rolling level-0 files
(flushWriteBuffer:209-260), triggers compaction, and accumulates the
CommitIncrement returned by prepareCommit (:263-278).

The memtable here is a list of column batches; "sorting the buffer" is the
same device merge kernel used everywhere else — flush = merge(concat(buffer)).
"""

from __future__ import annotations

import numpy as np

from ..data.batch import ColumnBatch
from ..metrics import carried, flush_metrics, span
from ..options import CoreOptions
from ..types import RowKind
from .compact import CompactResult, MergeTreeCompactManager
from .datafile import DataFileMeta, KeyValueFileWriterFactory
from .kv import KVBatch
from .manifest import CommitMessage

__all__ = ["MergeTreeWriter"]


class MergeTreeWriter:
    def __init__(
        self,
        partition: tuple,
        bucket: int,
        total_buckets: int,
        writer_factory: KeyValueFileWriterFactory,
        merge_executor,
        compact_manager: MergeTreeCompactManager | None,
        options: CoreOptions,
        restored_max_seq: int = -1,
        admission=None,
        debt_gate=None,
    ):
        self.partition = partition
        self.bucket = bucket
        self.total_buckets = total_buckets
        self.writer_factory = writer_factory
        self.merge = merge_executor
        self.compact_manager = compact_manager
        self.options = options
        self.seq = restored_max_seq + 1
        # admission control (core/admission.py): every buffered byte is
        # reserved against the shared WriteBufferController and released
        # exactly once — when the flush that drains it finishes encoding, or
        # when this writer is closed/abandoned (commit-conflict teardown).
        # _accounted tracks this writer's outstanding reservation so teardown
        # can release the remainder without double-counting what in-flight
        # flush workers already returned.
        self.admission = admission
        # debt-admission gate (ISSUE 12, PR 11 follow-up): a zero-arg
        # resolver returning the table's running AdaptiveCompactorService
        # (or None). Write-only writers have no inline compaction manager,
        # so every flush — the moment a new sorted run is born — first
        # admits against the service's read-amp ceiling and settles the
        # charge once the run's files land. Resolved per flush so a service
        # started after this writer still bounds it.
        self.debt_gate = debt_gate
        self._accounted = 0
        self._slots_held = 0
        import threading

        self._acct_lock = threading.Lock()
        self._buffer: list[KVBatch] = []
        self._buffered_rows = 0
        self._buffered_bytes = 0
        self._buffer_seq_ordered = True
        # read-your-writes visibility: batches drained from the memtable but
        # whose flush has not yet landed level-0 files stay listed here, so
        # delta_snapshot never has a blind window between flush_dispatch
        # clearing the buffer and flush_complete publishing _new_files
        self._inflight_delta: list[KVBatch] = []
        self._new_files: list[DataFileMeta] = []
        self._compact_before: list[DataFileMeta] = []
        self._compact_after: list[DataFileMeta] = []
        self._changelog: list[DataFileMeta] = []
        self._compact_changelog: list[DataFileMeta] = []
        # pipelined flush (parallel/pipeline.py consumer 3): auto-flushes
        # triggered by write() offload the merge-resolve + file encode (+
        # any resulting compaction) to a single background worker, so the
        # next memtable fills while the previous one encodes. One worker +
        # FIFO keeps the levels/compaction state transitions in exactly the
        # sequential order — output is bit-identical. prepare_commit (and the
        # public flush()) is the barrier; worker errors surface there.
        from ..parallel.pipeline import pipeline_config

        self._async_flush = pipeline_config(options)[0] > 0
        self._flush_pool = None
        self._flush_pending: list = []

    # ---- ingest --------------------------------------------------------
    def write(self, data: ColumnBatch, kinds: np.ndarray | None = None) -> None:
        """Append a batch of rows; sequence numbers are assigned in arrival
        order (MergeTreeWriter.write: newSequenceNumber per record)."""
        n = data.num_rows
        if n == 0:
            return
        kv = KVBatch.from_rows(data, self.seq, kinds)
        self._reserve(kv.byte_size())  # may raise: seq/buffer untouched
        self.seq += n
        self._buffer.append(kv)
        self._buffered_rows += n
        self._buffered_bytes += kv.byte_size()
        if self._should_flush():
            self._flush_async()

    def write_kv(self, kv: KVBatch) -> None:
        if kv.num_rows == 0:
            return
        self._reserve(kv.byte_size())  # may raise: buffer untouched
        # externally assigned seqs may interleave: disable the stability
        # shortcut for this memtable generation
        self._buffer_seq_ordered = False
        self._buffer.append(kv)
        self.seq = max(self.seq, int(kv.seq.max()) + 1)
        self._buffered_rows += kv.num_rows
        self._buffered_bytes += kv.byte_size()
        if self._should_flush():
            self._flush_async()

    # ---- admission accounting ------------------------------------------
    def _reserve(self, nbytes: int) -> None:
        """Admission for nbytes of memtable. Over the stop trigger, first
        drain OUR OWN memtable through the (offloaded) flush — freeing the
        share this writer itself holds — then fall back to the bounded
        blocking reserve (which raises WriterBackpressureError on deadline,
        with nothing buffered and self.seq untouched)."""
        if self.admission is None:
            return
        if not self.admission.try_reserve(nbytes):
            if self._buffered_bytes > 0:
                self._flush_async()
            self.admission.reserve(nbytes)
        with self._acct_lock:
            self._accounted += nbytes

    def _acct_release(self, nbytes: int) -> None:
        if self.admission is None or nbytes <= 0:
            return
        with self._acct_lock:
            nbytes = min(nbytes, self._accounted)
            self._accounted -= nbytes
        self.admission.release(nbytes)

    def _acct_release_all(self) -> None:
        if self.admission is None:
            return
        with self._acct_lock:
            n, self._accounted = self._accounted, 0
        self.admission.release(n)

    def _should_flush(self) -> bool:
        """Byte budget first (reference MemorySegmentPool accounts bytes —
        wide rows must not blow host memory before a row cap), row cap as
        the secondary bound."""
        return (
            self._buffered_bytes >= self.options.write_buffer_size
            or self._buffered_rows >= self.options.write_buffer_rows
        )

    # ---- flush ---------------------------------------------------------
    def flush(self) -> None:
        """Synchronous barrier: drain the memtable AND wait for every
        offloaded flush to finish (errors from background encodes re-raise
        here). Same post-conditions as the sequential path."""
        self._flush_async()
        self._drain_flushes()

    def _flush_async(self) -> None:
        """Drain the memtable; run the complete phase on the flush worker
        when pipelining is on (so the caller returns to filling the next
        memtable), inline otherwise. FIFO on one worker = sequential order."""
        from ..parallel.mesh_exec import current_mesh_context

        state = self.flush_dispatch()
        if state is None:
            return
        if not self._async_flush or current_mesh_context() is not None:
            self.flush_complete(state)
            return
        if self.admission is not None and not self.admission.flush_begin():
            # pending-flush depth cap held for the full block timeout: a slow
            # encoder must not queue unbounded memtables — encode inline, the
            # caller pays (that IS the backpressure)
            self.flush_complete(state)
            return
        if self.admission is not None:
            with self._acct_lock:
                self._slots_held += 1
        if self._flush_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            from ..parallel.pipeline import FLUSH_THREAD_PREFIX

            self._flush_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix=FLUSH_THREAD_PREFIX
            )
        from ..metrics import pipeline_metrics

        import time as _time

        g = pipeline_metrics()
        busy = g.histogram("flush_busy_ms")
        g.counter("splits_prefetched").inc()

        def run():
            t0 = _time.perf_counter()
            try:
                self.flush_complete(state)
            finally:
                if self.admission is not None:
                    with self._acct_lock:
                        self._slots_held -= 1
                    self.admission.flush_end()
                busy.update((_time.perf_counter() - t0) * 1000)

        # carried: the worker's flush, file.write and compact spans name the
        # operation and the span (prepare_commit, write) that handed them over
        self._flush_pending.append(self._flush_pool.submit(carried(run)))

    def _drain_flushes(self) -> None:
        """Wait for offloaded flushes; the FIRST failure re-raises after the
        rest were cancelled/awaited (a failed flush must not silently let a
        later one keep mutating levels)."""
        pending, self._flush_pending = self._flush_pending, []
        if not pending:
            return
        error = None
        with span("flush.wait", flushes=len(pending)):
            for f in pending:
                if error is not None:
                    f.cancel()
                    continue
                try:
                    f.result()
                except BaseException as exc:  # noqa: BLE001 — re-raised below
                    error = exc
        if error is not None:
            self._shutdown_flush_pool()
            raise error

    def _shutdown_flush_pool(self) -> None:
        if self._flush_pool is not None:
            self._flush_pool.shutdown(wait=True, cancel_futures=True)
            self._flush_pool = None
        if self.admission is not None:
            # with the pool down, any slot still held belongs to a flush
            # that was cancelled before running (its run() never reached
            # flush_end) — return those so the depth cap cannot wedge
            with self._acct_lock:
                slots, self._slots_held = self._slots_held, 0
            for _ in range(slots):
                self.admission.flush_end()

    def close(self) -> None:
        """Release the flush worker without committing. Pending background
        errors are swallowed (close is the abandon path; prepare_commit is
        where failures must surface). Every byte this writer still holds
        reserved — undrained memtable, a cancelled flush's batch, a failed
        dispatch — returns to the admission controller EXACTLY once here, so
        abandoning a bucket after a commit conflict re-admits blocked rivals
        instead of leaking budget."""
        for f in self._flush_pending:
            f.cancel()
        try:
            for f in self._flush_pending:
                if not f.cancelled():
                    f.exception()
        finally:
            self._flush_pending = []
            self._shutdown_flush_pool()  # also returns cancelled flushes' depth slots
            self._acct_release_all()
            self._inflight_delta.clear()

    def flush_dispatch(self):
        """Phase 1 of a (possibly mesh-batched) flush: drain the memtable,
        persist the input changelog, and dispatch the merge. Under an active
        mesh context the merge job is only enqueued — every bucket's job
        runs in one batched mesh call when the first flush_complete resolves.

        Any offloaded flush_complete still in flight lands first (and its
        error surfaces here): at most one flush is ever pending, so every
        caller — including the mesh path's direct dispatch/complete — sees
        levels/compaction state in strict flush order. The overlap window is
        the memtable fill between two flushes, which is the point."""
        self._drain_flushes()
        if not self._buffer:
            return None
        with span("flush", rows_in=self._buffered_rows):
            return self._flush_dispatch()

    def _flush_dispatch(self):
        gate = self.debt_gate() if self.debt_gate is not None else None
        if gate is not None:
            # block (bounded) while this bucket's projected sorted-run count
            # sits at/over the read-amp ceiling, then charge the in-flight
            # run this flush is about to create; flush_complete settles. A
            # timeout proceeds — the breach is the scheduler's to drain, the
            # gate must never wedge ingest on a stalled compactor.
            from ..options import CoreOptions as _CO

            timeout_ms = self.options.options.get(_CO.COMPACTION_ADAPTIVE_INGEST_GATE_TIMEOUT)
            gate.admit([(self.partition, self.bucket)], timeout_s=timeout_ms / 1000.0)
        from ..resilience.faults import crash_point

        # memtable full, nothing drained: a kill here loses only rows no
        # commit ever acknowledged
        crash_point("flush:before-dispatch")
        if len(self._buffer) > 1:
            with span("concat", rows=self._buffered_rows, columns=len(self._buffer[0].data.schema.fields)):
                kv = KVBatch.concat(self._buffer)
        else:
            kv = self._buffer[0]
        flush_metrics().counter("rows_in").inc(kv.num_rows)
        drained_bytes = self._buffered_bytes
        self._inflight_delta.append(kv)  # visible to delta_snapshot until the L0 files land
        self._buffer.clear()
        self._buffered_rows = 0
        self._buffered_bytes = 0
        from ..options import ChangelogProducer

        producer = self.options.changelog_producer
        if producer == ChangelogProducer.INPUT:
            # the raw input IS the changelog (reference: input producer
            # persists the flushed buffer as changelog files)
            self._changelog.extend(
                self.writer_factory.write(
                    kv, level=0, file_source="append", prefix="changelog", sorted_input=False
                )
            )
        # memtable rows arrive in seq order: stability replaces seq lanes
        buffer_seq_ordered = self._buffer_seq_ordered
        handle = self.merge.merge_async(kv, seq_ascending=buffer_seq_ordered)
        self._buffer_seq_ordered = True
        return (handle, buffer_seq_ordered, drained_bytes, gate, kv)

    def flush_complete(self, state) -> None:
        """Phase 2: resolve the merge and write level-0 files + changelog,
        then trigger compaction. The batch's buffer reservation returns to
        the admission controller when the encode lands (or fails) — that is
        the moment the bytes stop being host-memory the flush pipeline owes.
        The debt-gate charge settles here too: landed when the level-0 run's
        files exist, abandoned when the flush failed."""
        handle, buffer_seq_ordered, drained_bytes, gate, kv = state
        landed = False
        try:
            with span("flush") as sp:
                files = self._flush_complete_inner(handle, buffer_seq_ordered)
                rows_out, nbytes = sum(f.row_count for f in files), sum(f.file_size for f in files)
                sp.add(rows_out=rows_out, files=len(files))
            g = flush_metrics()
            g.counter("rows_out").inc(rows_out)
            g.counter("files").inc(len(files))
            g.counter("bytes").inc(nbytes)
            if self.compact_manager is not None and not self.options.write_only:
                # a round of compaction is a span of its own (compact), after the flush's
                for f in files:
                    self.compact_manager.levels.level0.insert(0, f)
                self._maybe_compact()
            landed = True
        finally:
            self._acct_release(drained_bytes)
            try:
                # the L0 files (or the failure) are published: the raw batch
                # leaves the read-your-writes in-flight window
                self._inflight_delta.remove(kv)
            except ValueError:
                pass  # close() may have cleared the window already
            if gate is not None:
                gate.settle([(self.partition, self.bucket)], landed=landed)

    def _flush_complete_inner(self, handle, buffer_seq_ordered) -> list[DataFileMeta]:
        """The merge resolved and the level-0 files (and changelog) written;
        returns the level-0 files."""
        merged = self.merge.merge_resolve(handle)
        from ..options import ChangelogProducer

        producer = self.options.changelog_producer
        from ..options import CoreOptions

        lookup_wait = self.options.options.get(CoreOptions.CHANGELOG_PRODUCER_LOOKUP_WAIT)
        if producer == ChangelogProducer.LOOKUP and lookup_wait:
            # exact changelog at WRITE time: look up the previous visible
            # value of each incoming key (reference LookupChangelogMerge-
            # FunctionWrapper / LookupMergeTreeCompactRewriter — here the
            # "lookup" is a vectorized merge-read of the overlapping files
            # diffed against the new state with the same kernel as the
            # full-compaction producer).  changelog-producer.lookup-wait=false
            # defers production to the next compaction (store.py arms the
            # compaction rewriter's changelog emitter for that case) so the
            # commit never waits on the lookup.
            cl = self._lookup_changelog(merged, buffer_seq_ordered)
            if cl.num_rows:
                self._changelog.extend(
                    self.writer_factory.write(
                        cl, level=0, file_source="append", prefix="changelog", sorted_input=False
                    )
                )
        files = self.writer_factory.write(merged, level=0, file_source="append")
        from ..resilience.faults import crash_point

        # level-0 files durable but referenced by no snapshot yet: a kill
        # here strews orphan data files for remove_orphan_files to reclaim
        crash_point("flush:files-written")
        self._new_files.extend(files)
        return files

    def _lookup_changelog(self, merged: KVBatch, buffer_seq_ordered: bool = True) -> KVBatch:
        """Diff the bucket's visible state before vs after this flush,
        restricted to the flushed key range."""
        from ..data.keys import encode_key_lanes, exact_string_pool
        from ..types import TypeRoot
        from .changelog import full_compaction_changelog
        from .read import MergeFileSplitRead

        if merged.num_rows == 0 or self.compact_manager is None:
            return merged.slice(0, 0)
        key_names = self.merge.key_names
        lo = tuple(merged.data.column(k).values[0] for k in key_names)
        hi = tuple(merged.data.column(k).values[-1] for k in key_names)
        overlapping = [
            f
            for f in self.compact_manager.levels.all_files()
            if not (f.max_key < lo or f.min_key > hi)
        ]
        reader = MergeFileSplitRead(
            self.compact_manager.rewriter.reader_factory, self.merge, key_names
        )
        before = reader.read_kv(
            overlapping, drop_delete=True, deletion_vectors=self.compact_manager.rewriter.deletion_vectors
        )
        # after = before + new batch merged; stability only applies when the
        # buffer's seqs were monotone (write_kv may interleave external seqs)
        after = self.merge.merge(
            KVBatch.concat([before, merged]), seq_ascending=buffer_seq_ordered
        ).drop_deletes()
        pools = {}
        for k in key_names:
            root = merged.data.schema.field(k).type.root
            if root in (TypeRoot.CHAR, TypeRoot.VARCHAR, TypeRoot.BINARY, TypeRoot.VARBINARY):
                pools[k] = exact_string_pool([before.data.column(k), after.data.column(k)])
        lanes_before = encode_key_lanes(before.data, key_names, pools)
        lanes_after = encode_key_lanes(after.data, key_names, pools)
        return full_compaction_changelog(
            before,
            after,
            lanes_before,
            lanes_after,
            row_deduplicate=self.options.options.get(CoreOptions.CHANGELOG_PRODUCER_ROW_DEDUPLICATE),
        )

    def _maybe_compact(self, full: bool = False) -> None:
        assert self.compact_manager is not None
        result = self.compact_manager.trigger_compaction(full=full)
        self._absorb(result)

    def compact(self, full: bool = False) -> None:
        """Explicit compaction (dedicated compact jobs / full-compaction)."""
        self.flush()
        if self.compact_manager is not None:
            self._maybe_compact(full=full)

    def compact_dispatch(self, full: bool = False):
        """Phase 1 of an explicit compaction (caller must have flushed)."""
        self._drain_flushes()  # levels must be settled before planning
        if self.compact_manager is None:
            return None
        return self.compact_manager.compact_dispatch(full)

    def compact_complete(self, state) -> None:
        if state is None or self.compact_manager is None:
            return
        self._absorb(self.compact_manager.compact_complete(state))

    def _absorb(self, result: CompactResult | None) -> None:
        if result is None or result.is_empty():
            return
        # cancel out files that this very commit created and then compacted
        new_names = {f.file_name for f in self._new_files}
        created_then_compacted = [f for f in result.before if f.file_name in new_names]
        self._compact_before.extend(f for f in result.before if f.file_name not in new_names)
        # files created and consumed within one commit still need ADD+DELETE
        # to keep the manifest chain consistent — reference keeps both too
        self._compact_before.extend(created_then_compacted)
        self._compact_after.extend(result.after)
        self._compact_changelog.extend(result.changelog)

    # ---- commit --------------------------------------------------------
    def prepare_commit(self) -> CommitMessage:
        try:
            self.flush()  # barrier: offloaded encodes land before the message builds
        finally:
            # torn down on the ERROR path too: a flush-worker failure
            # re-raised here must not leak the 1-worker paimon-flush
            # executor (the happy path shut it down; a dispatch-phase
            # failure — e.g. the input-changelog write — left it alive)
            self._shutdown_flush_pool()
        # a file produced by one compaction round and consumed by a later
        # round within the same commit cancels out of the message. Keyed by
        # (name, LEVEL), not name alone: an upgrade emits DELETE(F@k) +
        # ADD(F@higher) under ONE name — name-based cancel would erase the
        # whole chain, deleting the rewrite's inputs while never adding F
        # (silent row loss once the orphan sweep reclaims it). With the
        # level in the key only the true create-then-consume pair (F@k in
        # both lists) cancels, leaving DELETE inputs + ADD F@higher.
        before_keys = {(f.file_name, f.level) for f in self._compact_before}
        after_keys = {(f.file_name, f.level) for f in self._compact_after}
        cancel = before_keys & after_keys
        msg = CommitMessage(
            partition=self.partition,
            bucket=self.bucket,
            total_buckets=self.total_buckets,
            new_files=list(self._new_files),
            compact_before=[f for f in self._compact_before if (f.file_name, f.level) not in cancel],
            compact_after=[f for f in self._compact_after if (f.file_name, f.level) not in cancel],
            changelog_files=list(self._changelog),
            compact_changelog_files=list(self._compact_changelog),
        )
        self._new_files.clear()
        self._compact_before.clear()
        self._compact_after.clear()
        self._changelog.clear()
        self._compact_changelog.clear()
        return msg

    def delta_snapshot(self) -> tuple[list[KVBatch], list[DataFileMeta]]:
        """Point-in-time view of this writer's uncommitted state for the
        read-your-writes get tier: buffered memtable batches (plus any
        drained-but-not-yet-landed flush input) and the level-0 files no
        snapshot references yet. List copies — safe to take from a serving
        thread while this writer keeps ingesting (a row caught by BOTH an
        in-flight batch and its landed file resolves identically: same key,
        same sequence, same value)."""
        return list(self._buffer) + list(self._inflight_delta), list(self._new_files)

    @property
    def max_sequence_number(self) -> int:
        return self.seq - 1

    def health(self) -> dict:
        """Point-in-time writer state for TableWrite.health()."""
        return {
            "buffered_bytes": self._buffered_bytes,
            "buffered_rows": self._buffered_rows,
            "pending_flushes": len(self._flush_pending),
            "reserved_bytes": self._accounted,
        }
