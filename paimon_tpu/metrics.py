"""Engine-neutral metrics kernel.

Parity: /root/reference/paimon-core/.../metrics/ — MetricRegistry, groups,
Counter/Gauge/Histogram; instrumented scan/commit/compaction
(operation/metrics/ScanMetrics, CommitMetrics, CompactionMetrics). External
engines bridge this registry to their own metric systems, exactly like the
reference bridges to Flink/Spark.
"""

from __future__ import annotations

import contextvars
import itertools
import threading
import time
from typing import Callable

from jax.profiler import TraceAnnotation

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricGroup",
    "MetricRegistry",
    "registry",
    "span",
    "carried",
    "timed",
    "compaction_metrics",
    "datafile_metrics",
    "decode_metrics",
    "dict_metrics",
    "encode_metrics",
    "flush_metrics",
    "gateway_metrics",
    "get_metrics",
    "io_metrics",
    "join_metrics",
    "lanes_metrics",
    "merge_metrics",
    "mesh_metrics",
    "pallas_metrics",
    "pipeline_metrics",
    "read_metrics",
    "soak_metrics",
    "sql_metrics",
    "sub_metrics",
    "write_metrics",
]


class Counter:
    def __init__(self):
        self._v = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._v += n

    @property
    def count(self) -> int:
        return self._v


class Gauge:
    def __init__(self, fn: Callable[[], float] | None = None):
        self._fn = fn
        self._v: float = 0.0

    def set(self, v: float) -> None:
        self._v = v

    @property
    def value(self) -> float:
        return self._fn() if self._fn is not None else self._v


class Histogram:
    """Sliding-window histogram (reference uses a 100-sample window), with a
    lifetime `total` (samples ever) and `sum` beside the window: the window
    forgets, so only those two can be differenced over a stretch of time."""

    def __init__(self, window: int = 100):
        self.window = window
        self._values: list[float] = []
        self.total = 0
        self.sum = 0.0
        self._lock = threading.Lock()

    def update(self, v: float) -> None:
        with self._lock:
            self.total += 1
            self.sum += v
            self._values.append(v)
            if len(self._values) > self.window:
                self._values.pop(0)

    @property
    def count(self) -> int:
        return len(self._values)

    @property
    def mean(self) -> float:
        return sum(self._values) / len(self._values) if self._values else 0.0

    @property
    def max(self) -> float:
        return max(self._values) if self._values else 0.0

    @property
    def min(self) -> float:
        return min(self._values) if self._values else 0.0

    @property
    def last(self) -> float:
        """Most recent sample — per-operation readout for benches/tests."""
        return self._values[-1] if self._values else 0.0


class MetricGroup:
    def __init__(self, name: str, tags: dict[str, str] | None = None):
        self.name = name
        self.tags = tags or {}
        self.metrics: dict[str, object] = {}

    # get before setdefault: the read path asks for its counters at every
    # file and every merge, and setdefault alone builds (and drops) a new
    # metric with its lock on each call

    def counter(self, name: str) -> Counter:
        return self.metrics.get(name) or self.metrics.setdefault(name, Counter())  # type: ignore[return-value]

    def gauge(self, name: str, fn: Callable[[], float] | None = None) -> Gauge:
        return self.metrics.get(name) or self.metrics.setdefault(name, Gauge(fn))  # type: ignore[return-value]

    def histogram(self, name: str, window: int = 100) -> Histogram:
        return self.metrics.get(name) or self.metrics.setdefault(name, Histogram(window))  # type: ignore[return-value]


class MetricRegistry:
    def __init__(self):
        self.groups: dict[tuple, MetricGroup] = {}
        self._lock = threading.Lock()

    def group(self, name: str, **tags: str) -> MetricGroup:
        key = (name, tuple(sorted(tags.items())))
        with self._lock:
            if key not in self.groups:
                self.groups[key] = MetricGroup(name, tags)
            return self.groups[key]

    def snapshot(self) -> dict:
        with self._lock:  # group() inserts under it from other threads
            groups = list(self.groups.items())
        out: dict = {}
        for (name, tags), group in groups:
            entry = {}
            for mname, m in list(group.metrics.items()):
                if isinstance(m, Counter):
                    entry[mname] = m.count
                elif isinstance(m, Gauge):
                    entry[mname] = m.value
                elif isinstance(m, Histogram):
                    entry[mname] = {"count": m.count, "mean": m.mean, "max": m.max,
                                    "total": m.total, "sum": m.sum}
            out[name if not tags else f"{name}{dict(tags)}"] = entry
        return out

    def reset(self) -> None:
        with self._lock:
            self.groups.clear()


registry = MetricRegistry()


def decode_metrics() -> MetricGroup:
    """The decode{...} group (native parquet page-decode subsystem,
    paimon_tpu.decode). Canonical members — counters: pages_decoded,
    pages_skipped (dead under compressed-domain pushdown, never expanded),
    bytes_expanded (materialized value bytes), rows_pruned, files_native,
    files_fallback (fell back to the arrow decoder); histograms: file_ms
    (whole-file native decode wall millis), pushdown_ms (per row group).
    Resolved per call so registry.reset() in tests swaps the group out."""
    return registry.group("decode")


def dict_metrics() -> MetricGroup:
    """The dict{...} group (compressed-domain merge, paimon_tpu.ops.dicts +
    the code-domain reader mode in paimon_tpu.decode). Canonical members —
    counters: pools_unified (per-input sorted pools merged into a shared
    merge domain), codes_remapped (rows whose dictionary codes re-mapped
    through a unification/sort gather), rows_code_domain (rows delivered by
    a reader as dictionary codes instead of expanded strings),
    fallback_expanded (rows that fell back to the expanded-string path: a
    non-dictionary chunk, a pool past merge.dict-domain.pool-limit, or a
    consumer that needed real values); histogram: unify_ms (host wall
    millis unifying pools — object work at |pool| scale, never |rows|).
    Resolved per call so registry.reset() in tests swaps the group out."""
    return registry.group("dict")


def encode_metrics() -> MetricGroup:
    """The encode{...} group (native parquet page-encode subsystem,
    paimon_tpu.encode — the write-side mirror of decode{...}). Canonical
    members — counters: pages_written (data pages), bytes_written (file
    bytes produced natively), dict_pages (dictionary pages emitted),
    files_native, files_fallback (fell back to the arrow writer on an
    unsupported shape); histograms: encode_ms (whole-file native encode
    wall millis), stats_ms (chunk min/max statistics portion). Resolved per
    call so registry.reset() in tests swaps the group out."""
    return registry.group("encode")


def pipeline_metrics() -> MetricGroup:
    """The pipeline{...} group (pipelined split scheduler,
    paimon_tpu.parallel.pipeline). Canonical members — counter:
    splits_prefetched (items submitted ahead of the consumer); gauge:
    queue_depth_high_water (max items in flight — bounded by
    scan.prefetch-splits + 1, the memory high-water guard); histograms per
    stage: {stage}_busy_ms (worker wall time per item) and {stage}_wait_ms
    (consumer blocked waiting for the head-of-line item), stage in
    {scan, compact, flush}. Resolved per call so registry.reset() in tests
    swaps the group out."""
    return registry.group("pipeline")


def lanes_metrics() -> MetricGroup:
    """The lanes{...} group (key-lane compression layer, paimon_tpu.ops.lanes).
    Canonical members — counters: plans (merges planned), plans_from_columns
    (those of them planned and packed straight from integer key columns,
    ops.lanes.compress_key_columns: no lane matrix was built), lanes_in (logical
    uint32 key lanes entering the planner), lanes_out (physical sort operands
    after truncation + packing, incl. the OVC lane when present), bytes_saved
    (host->device key-lane bytes elided vs the uncompressed upload),
    ovc_merges (merges that carried an offset-value code lane through the
    sort). Resolved per call so registry.reset() in tests swaps the group
    out."""
    return registry.group("lanes")


def join_metrics() -> MetricGroup:
    """The join{...} group (device-side skew-aware joins, paimon_tpu.ops.
    join, surfaced through SQL JOIN and lookup joins). Canonical members —
    counters: joins (two-batch join_batches calls), index_probes (cached
    JoinIndex probe calls: the vectorized lookup path), rows_probed,
    rows_matched, hash_joins (single fused key operand: binary-search
    probe), sort_merge_joins (multi-operand keys through the
    sorted_segments seam), code_domain_joins (joins where at least one key
    column matched on unified dictionary codes with zero string
    materialization), skew_keys (heavy-hitter keys whose probe rows were
    split across partitions), skew_split_rows (probe rows so split);
    histograms: build_ms (key encode + lane planning), probe_ms (kernel +
    pair expansion). Resolved per call so registry.reset() in tests swaps
    the group out."""
    return registry.group("join")


def read_metrics() -> MetricGroup:
    """The read{...} group (TableRead.read_all, paimon_tpu.table.read).
    Canonical members, counters: ops (read_all calls), rows_in (records in
    the data files of the splits read, from the file metadata), rows_out
    (rows returned). rows_out / rows_in is the share of records that won
    their key. rows_gathered (winners, counted once a column gathered, seq
    and kind among them) and rows_gathered_from_parts (those of the columns
    that nobody concatenated: numpy-valued and code-backed value columns,
    taken from the per-file parts by Column.take_from_parts; not arrow-backed
    ones, whose chunks pyarrow's take joins inside, nor a column that fell
    back to the concatenation, nor the key columns, seq and kind). Both are
    counted by the gather of the keys-only pipeline (core/read.py).
    Resolved per call so registry.reset() in tests swaps the group out."""
    return registry.group("read")


def datafile_metrics() -> MetricGroup:
    """The datafile{...} group (paimon_tpu.core.datafile:
    KeyValueFileReaderFactory._decode, one data file read through its format
    and mapped onto the read schema, whichever decoder backs the format; a
    data-file cache hit decodes nothing and counts nothing; and
    KeyValueFileWriterFactory.write). Canonical
    members, counters: files_decoded, rows_decoded, bytes_decoded (the
    decoded batch's KVBatch.byte_size()); files_written (data files a
    KeyValueFileWriterFactory.write returned) and files_written_on_pool
    (those of them written as tasks of the shared pool: the files of a
    write that is cut into several, called from a thread that is not the
    pool's). The decode{...} group stays the native parquet decoder's own.
    Resolved per call so registry.reset() in tests swaps the group out."""
    return registry.group("datafile")


def merge_metrics() -> MetricGroup:
    """The merge{...} group (device merge dispatch, paimon_tpu.ops.merge),
    counted from shapes at every kernel call and every download, on any
    backend. Canonical members, counters: merges (merges handed to the
    device: a key-range tiled merge is one), rows_in (valid rows in them),
    tiles (sort instances: the tiles of a tiled merge, else one), pad_rows
    (rows allocated on the device beyond the valid ones: rows sorted for
    nothing), h2d_bytes (bytes of the host arrays passed to the jitted
    call), d2h_bytes (bytes of the arrays fetched back), winners (rows the
    resolved selections name). Resolved per call so registry.reset() in
    tests swaps the group out."""
    return registry.group("merge")


def mesh_metrics() -> MetricGroup:
    """The mesh{...} group (mesh-sharded execution layer,
    paimon_tpu.parallel.mesh_exec). Canonical members — counters:
    buckets_sharded (per-bucket merge jobs executed through the mesh),
    shards (shard_map / key-axis collective invocations), pad_rows (padding
    overhead: allocated minus valid rows across batched calls),
    exchange_rows (rows moved through key-axis range-shuffle collectives);
    histograms: device_busy_ms (wall millis per batched device call),
    feeder_wait_ms (consumer blocked on the host-side split feeder).
    Resolved per call so registry.reset() in tests swaps the group out."""
    return registry.group("mesh")


def soak_metrics() -> MetricGroup:
    """The soak{...} group (writer flow control, core.admission, and the
    traffic-soak harness, service.soak). Canonical members — counters:
    commits_ok (committer rounds fully landed), commits_retried (CAS retry
    rounds absorbed across commits), commits_conflict_replanned (conflict
    events survived by abandoning stolen buckets or adopting the landed
    APPEND phase), writes_throttled (admissions that blocked at the
    stop trigger or the pending-flush cap), writes_rejected (throttled
    writes that hit write.buffer.block-timeout and raised
    WriterBackpressureError), procs_spawned / procs_killed /
    procs_respawned (process-grain soak supervisor: writer/reader OS
    processes started, kill -9'd at crash points or at random, and brought
    back), crash_recoveries (respawned writers that resolved a landed-but-
    unacked commit from the snapshot chain instead of replaying it),
    shed_requests (ingest requests answered with a typed BUSY by a network
    server while the writer was throttling/rejecting); gauges: read_p50_ms,
    read_p99_ms (snapshot read latency percentiles, set by the soak
    harness); histogram: backpressure_ms (time writers spent blocked in
    admission). Resolved per call so registry.reset() in tests swaps the
    group out."""
    return registry.group("soak")


def pallas_metrics() -> MetricGroup:
    """The pallas{...} group (the boundary-sweep kernel, paimon_tpu.ops.
    pallas_kernels, routed by sort-engine=pallas). Canonical members —
    counters: kernels_launched (merge dispatches routed through the pallas
    engine), tiles (pallas grid steps: one per _BLOCK rows of the
    post-lax.sort boundary sweep); histogram: kernel_ms (wall millis of
    synchronously-resolved dispatches: merge_plan). Resolved per call so
    registry.reset() in tests swaps the group out."""
    return registry.group("pallas")


def write_metrics() -> MetricGroup:
    """The write{...} group (TableWrite, paimon_tpu.table.write). Canonical
    members, counters: rows (rows handed to TableWrite.write), commits
    (prepare_commit calls). Resolved per call so registry.reset() in tests
    swaps the group out."""
    return registry.group("write")


def flush_metrics() -> MetricGroup:
    """The flush{...} group (MergeTreeWriter, paimon_tpu.core.writer: one
    memtable through the merge into level-0 files). Canonical members,
    counters: rows_in (memtable rows drained), rows_out (rows of the level-0
    files landed: one a key), files, bytes (those files' sizes). Resolved per
    call so registry.reset() in tests swaps the group out."""
    return registry.group("flush")


def compaction_metrics() -> MetricGroup:
    """The compaction{...} group (LSM compaction execution, core.compact,
    plus the adaptive scheduler, table.compactor.AdaptiveCompactorService).
    Canonical members — counters: rounds (trigger_compaction calls, whether
    or not the strategy picked a unit), rows_in / bytes_in / rows_out /
    files_out / bytes_out (rows of the files a rewrite read and their
    decoded bytes as its read head joined them, and rows, count and sizes
    of the files it wrote: an upgrade moves a file between levels and counts
    in none of them), compactions, files_rewritten (execution
    side, incremented per committed rewrite), adaptive_runs (buckets the
    adaptive scheduler compacted), deferred_buckets (buckets with pending
    sorted runs the policy deliberately left for later — cold or below
    trigger), adaptive_conflicts (adaptive rounds abandoned to a rival
    commit), admission_waits (ingest commits that blocked in the service's
    debt-admission gate because a target bucket sat at/over the read-amp
    ceiling); gauges: debt_files / debt_bytes (files and bytes above one
    run per bucket, summed over buckets — the compaction debt the
    scheduler is draining), read_amplification_p99 (p99 of per-bucket
    sorted-run counts at the last observation — the bound
    compaction.adaptive.read-amp-ceiling enforces); histogram: duration_ms
    (per compaction execution). Resolved per call so registry.reset() in
    tests swaps the group out."""
    return registry.group("compaction")


def get_metrics() -> MetricGroup:
    """The get{...} group (batched point-lookup serving, paimon_tpu.table.
    get + lookup.index, surfaced as LocalTableQuery.get_batch, the KV
    server's get_batch method and Flight do_action("get_batch")). Canonical
    members — counters: gets (probe keys served, found or not), keys_probed
    (key x surviving-file probe work actually executed), files_pruned (data
    files skipped with NO data IO: key-range or bloom key-index verdict),
    index_hits (files whose PTIX key bloom was consulted), memtable_hits
    (keys whose winning row came from the read-your-writes delta tier:
    an attached writer's memtable or its not-yet-committed level-0 files),
    busy_rejected (get_batch requests a server answered with a typed BUSY
    because lookup.get.max-inflight was saturated); histogram: probe_ms
    (end-to-end get_batch wall millis per call); gauge: p99_us (per-key p99
    latency in microseconds, set by the serving soak / benchmark).
    Resolved per call so registry.reset() in tests swaps the group out."""
    return registry.group("get")


def io_metrics() -> MetricGroup:
    """The io{...} group (resilience subsystem). Canonical members —
    counters: retries (transient faults absorbed by RetryingFileIO),
    giveups (ops that exhausted fs.retry.max-attempts), timeouts (ops that
    blew the fs.io.timeout deadline), cleanup_failures (non-fatal failures
    while deleting tmp/abandoned files in commit cleanup / expire / orphan
    sweep), orphans_removed; histogram: backoff_ms (individual retry
    sleeps). Resolved per call so registry.reset() in tests swaps the group
    out."""
    return registry.group("io")


def cluster_metrics() -> MetricGroup:
    """The cluster{...} group (coordinator/worker mesh execution,
    paimon_tpu.service.cluster). Canonical members — counters:
    workers_registered (worker registrations, respawned incarnations
    included), rounds_committed (ingest rounds the coordinator committed on
    behalf of workers), commits_rejected_stale (shipped CommitMessages
    refused because a bucket's assignment epoch advanced past the shipper's
    — the reassignment fence that prevents double-apply), reassignments
    (bucket ownership moves after a missed-heartbeat death), compact_tasks
    (compaction decisions dispatched to owning workers),
    compact_commits (worker-executed compaction results the coordinator
    committed), compact_conflicts (shipped compaction results abandoned to
    a rival commit), admit_denied (worker admit RPCs answered not-admitted
    because a target bucket sat at/over the read-amp ceiling — the
    cluster-wide debt gate), charges_released (in-flight debt charges
    dropped when their owning worker died), serve_gets (get_batch requests
    served by worker serving planes), serve_subscribe_polls (subscribe
    long-polls served by workers), join_parts_served (distributed join
    partitions executed on workers), rescales (completed cross-worker
    bucket rescales: schema bump + OVERWRITE snapshot landed and routes
    republished), handoffs (planned worker admits/retires that moved bucket
    ranges without a death timeout), replica_reads (serve reads a client
    routed to a non-primary replica owner). Gauges: workers_live,
    buckets_assigned, replicas_active (bucket->replica grants currently
    live). Resolved per call so registry.reset() in tests swaps the group
    out."""
    return registry.group("cluster")


def sql_metrics() -> MetricGroup:
    """The sql{...} group (distributed SQL scatter-gather,
    paimon_tpu.sql.cluster + the shared GROUP BY segment-reduce in
    sql.select / ops.aggregates). Canonical members — counters: fragments
    (per-worker scan fragments dispatched), fragments_retried (fragments
    re-dispatched after a worker death or connection loss),
    partials_combined (worker partial-aggregate payloads folded at the
    coordinator), rows_reduced_device (input rows reduced by the jitted
    segment-reduce kernel — single-process GROUP BY and worker partials
    both count; the numpy twin does not), code_domain_groups (groups whose
    keys travelled coordinator-ward as dictionary codes + pruned pools,
    never expanded), rows_streamed (non-aggregate rows gathered back
    Arrow-encoded), fragment_cache_hits (aggregate queries answered from
    the coordinator's fragment-result cache — same snapshot, same
    bucket-layout epoch, same fragment signature — without any worker RPC),
    shuffle_rounds (GROUP BY queries that combined via worker↔worker
    shuffle exchange instead of at the coordinator), parts_exchanged
    (nonempty group-domain hash partitions shipped worker→worker over
    exchange_part), exchange_bytes (approximate wire bytes of those
    parts), shuffle_retried (shuffle recovery actions: a range re-homed
    off a dead owner, or a missing part reshipped/re-executed);
    histograms: scatter_ms (dispatch + worker execution + gather wall
    millis per query), combine_ms (coordinator-side SERIAL combine stage
    millis per aggregate query: partial payload decode + second-stage
    unify/reduce — or, under shuffle, reduced-range decode + concat —
    + final batch assembly; RPC wait excluded, so classic vs shuffle
    readings compare the exact work the shuffle plane moves off the
    coordinator), shuffle_ms (scatter + exchange + per-range fold +
    concat wall millis per shuffled aggregate).
    Resolved per call so registry.reset() in tests swaps the group out."""
    return registry.group("sql")


def gateway_metrics() -> MetricGroup:
    """The gateway{...} group (multi-tenant front door,
    paimon_tpu.service.gateway). Canonical members — counters: requests
    (every request entering the gateway, any kind), admitted (requests that
    passed per-tenant QoS admission), sheds_typed (requests refused with a
    canonical ShedInfo — tenant budget, write backpressure, subscriber
    shed), sheds_untyped (client-observed failures under pressure that were
    NOT a typed shed; the storm harness counts these and asserts ZERO),
    hedges_issued (read RPCs re-issued to a secondary worker past
    gateway.hedge.deadline-ms), hedges_won (hedges where the secondary's
    answer was used), hedges_cancelled (loser attempts aborted after a
    winner returned), route_failovers (RPCs or bucket-owner lookups that
    fell over to a live secondary because the routed worker was dead or
    mid-respawn — the mega soak's kill schedule makes these routine; each
    one is a request SERVED, not shed); histograms: put_ms / get_batch_ms / subscribe_ms /
    sql_ms (per-kind gateway wall millis, all tenants mixed — the
    per-tenant decayed percentiles live in Gateway.slo()). Resolved per
    call so registry.reset() in tests swaps the group out."""
    return registry.group("gateway")


def sub_metrics() -> MetricGroup:
    """The sub{...} group (streaming CDC subscription service,
    paimon_tpu.service.subscription). Canonical members — gauges:
    subscribers (live subscribers across hubs), lag_snapshots (max over
    subscribers of frontier minus its next-expected snapshot — how far the
    slowest live reader trails the chain), queue_high_water (max batches
    observed in any subscriber queue, bounded by subscription.queue-depth);
    counters: batches_fanned (ChangelogBatch deliveries: one per subscriber
    per snapshot, live fan-out and catch-up replay both count),
    rows_fanned (rows delivered, rows x subscribers), decode_reuse_hits
    (deliveries that reused an already-decoded batch: live fan-out beyond
    the first subscriber plus catch-up reads served from the data-file
    cache — the decode-once proof, vs decode{pages_decoded} which stays
    flat in subscriber count), shed_subscribers (slow consumers shed with
    the typed SubscriberShedError carrying their durable restart offset).
    Resolved per call so registry.reset() in tests swaps the group out."""
    return registry.group("sub")


class timed:
    """Context manager recording wall millis into a histogram."""

    def __init__(self, histogram: Histogram):
        self.histogram = histogram

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.histogram.update((time.perf_counter() - self._t0) * 1000)
        return False


# ---- spans ---------------------------------------------------------------
# The program's spans ARE profiler annotations: a span exists in a trace
# exactly when a profiler session is open (jax.profiler.start_trace or
# start_server), on the clock of the device's own events, and is nothing but
# a few hundred nanoseconds when none is. docs/tracing.md has the names.

SPAN_PREFIX = "pt:"
_OP_IDS = itertools.count(1)
# (operation id, name of the open span, the open span or None on a thread
# that was handed the first two): what a span opened now is caused by
_CURRENT: contextvars.ContextVar[tuple] = contextvars.ContextVar("paimon_tpu_span", default=(0, "", None))


class span:
    """`with span("decode.file", format="orc") as sp: ...; sp.add(rows=n)`

    Opens the TraceAnnotation `pt:<name>` with the stats `op` (the id of the
    operation it works for, 0 outside one), `parent` (the name of the span
    that caused it, absent at the top) and the keyword numbers or strings
    given. `add` sums numbers known only later (rows decoded, tiles, bytes)
    onto the span; code deeper in the call reaches the innermost open span
    of its thread through `span.current()`. `new_op=True` allots the next
    operation id (TableRead.read_all does; TableWrite at the first write of a
    checkpoint); `op=<id>` continues an operation that an earlier call began
    (TableWrite.prepare_commit takes up the id its writes were given).
    `histogram=` also records the span's wall milliseconds there, traced or
    not: the one clock read for a timing on the read path."""

    __slots__ = ("name", "op", "_note", "_token", "_sums", "_histogram", "_t0")

    def __init__(self, name: str, histogram: Histogram | None = None, new_op: bool = False, op: int = 0, **stats):
        current, parent, _ = _CURRENT.get()
        self.name = name
        self.op = next(_OP_IDS) if new_op else (op or current)
        self._sums: dict | None = None
        self._histogram = histogram
        self._note = TraceAnnotation(SPAN_PREFIX + name, op=self.op, parent=parent, **stats)

    def __enter__(self) -> "span":
        self._token = _CURRENT.set((self.op, self.name, self))
        self._note.__enter__()
        if self._histogram is not None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._histogram is not None:
            self._histogram.update((time.perf_counter() - self._t0) * 1000)
        if self._sums:
            self._note.set_metadata(**self._sums)
        self._note.__exit__(*exc)
        _CURRENT.reset(self._token)
        return False

    def add(self, **numbers) -> None:
        sums = self._sums
        if sums is None:
            sums = self._sums = {}
        for key, n in numbers.items():
            sums[key] = sums.get(key, 0) + n

    @staticmethod
    def current() -> "span | None":
        """The innermost span open on this thread (None outside any, and on
        a pool thread that has opened none of its own)."""
        return _CURRENT.get()[2]


def carried(fn: Callable) -> Callable:
    """`fn` for a pool thread: it runs with this thread's operation id and
    open span's name, so a span opened over there (a file decoded on a
    worker) names the operation and the span that asked for it. A ContextVar
    does not cross into pool threads by itself."""
    op, name, _ = _CURRENT.get()

    def run(*args):
        token = _CURRENT.set((op, name, None))
        try:
            return fn(*args)
        finally:
            _CURRENT.reset(token)

    return run
