"""Production traffic soak: concurrent writers, churn, verified reads.

Every resilience piece of this system exists in isolation — RetryingFileIO +
commit auto-retry + orphan sweep, streaming reads, offloaded flushes, the
mesh engine, and (this PR) writer admission control. The soak harness is
where they prove they compose: N committer threads on disjoint AND
overlapping buckets, M reader threads asserting snapshot-consistent scans
against a serialized oracle log, a dedicated full-compactor and a snapshot
expirer churning underneath, all over a fault-injecting filesystem at a
sustained op rate, with one shared `WriteBufferController` modelling the
host-memory budget ("Fast Updates on Read-Optimized Databases" assumes the
delta never outruns the merge; this is the machinery that makes it true).

Consistency protocol. Writers commit through the real snapshot-CAS path and
record every LANDED commit in the `OracleLog` under one lock:
(append-snapshot-id -> {key: value}). Keyspaces are disjoint per writer
(key = writer_id * KEYSPACE + n) so cross-writer merge order is irrelevant,
while updates WITHIN a writer are ordered by its monotone sequence numbers —
the expected row set at snapshot S is therefore exactly the fold of all
recorded events with id <= S, in id order. A reader pins snapshot S
(scan.snapshot-id), scans, waits for the oracle to cover every soak APPEND
snapshot <= S (the record happens microseconds after commit() returns), and
asserts the scanned row set EQUALS the fold. A commit that raises may still
have landed its APPEND phase (conflict on the COMPACT half, a lost rename
ack, a crash-replay) — `find_landed_append` resolves the truth from the
snapshot chain, so the oracle counts exactly what the table counts: no lost
rows, no duplicated rows.

End of soak: drain writers, disable faults, full-compact once, assert the
final scan equals the oracle fold and the physical row count matches, then
run the orphan sweep with threshold 0 and assert the on-disk file set is
exactly the reachable closure (zero leaked files) — and that the sweep
removed nothing a reader can still see.

Run directly:  python -m paimon_tpu.service.soak [base_dir]
"""

from __future__ import annotations

import os
import threading
import time
import traceback
from dataclasses import dataclass, field

import numpy as np

from ..types import BIGINT, DOUBLE, RowType

# the oracle pieces live in service/oracle.py (shared with proc_soak,
# cluster and mega_soak); re-exported here for back-compat
from .oracle import OracleLog, find_landed_append, sweep_and_audit

__all__ = [
    "SoakConfig",
    "OracleLog",
    "SoakHarness",
    "run_soak",
    "find_landed_append",
    "sweep_and_audit",
]

SCHEMA = RowType.of(("k", BIGINT()), ("v", DOUBLE()))
KEYSPACE = 10_000_000  # per-writer key stride: keyspaces never collide


@dataclass
class SoakConfig:
    """Knobs for one soak run. `from_table_options` maps the soak.* table
    options onto the same fields so a run is reproducible from table config
    alone; the CLI/bench/tests override programmatically."""

    duration_s: float = 45.0
    writers: int = 3
    readers: int = 2
    buckets: int = 4
    fault_possibility: int = 0  # 1/N ops fail (20 = 5%); 0 = off
    seed: int = 0
    rows_per_commit: int = 400
    write_chunk_rows: int = 100  # rows per TableWrite.write call
    update_fraction: float = 0.3  # fraction of a round re-writing own keys
    compact_every: int = 4  # full-compact every Nth commit per writer
    compactor_pause_s: float = 0.4
    expire_every_s: float = 1.5
    # the churn compactor: False = periodic all-bucket full compaction;
    # True = the LUDA-style adaptive scheduler (table.compactor.
    # AdaptiveCompactorService) draining debt by heat/read-amp priority.
    # PAIMON_TPU_SOAK_ADAPTIVE=1 flips the default (the verify.sh soak
    # stage runs with it on).
    adaptive: bool = field(
        default_factory=lambda: os.environ.get("PAIMON_TPU_SOAK_ADAPTIVE", "") == "1"
    )
    mesh: bool = False
    # flow control (the shared WriteBufferController)
    backpressure: bool = True
    max_memory: int = 512 * 1024
    stop_trigger: float = 0.6
    block_timeout_ms: int = 30_000
    max_pending_flushes: int = 2
    # point-get storm (ISSUE 13): getter threads running batched gets with
    # a scalar-lookup()-loop oracle, a read-your-writes checker committing
    # through an attached TableWrite, and (get_server) a KvQueryServer the
    # getters deliberately overload to prove typed-BUSY shedding
    getters: int = 0
    get_batch_keys: int = 512
    get_oracle_keys: int = 16  # scalar lookups verified per round
    ryw: bool = True  # read-your-writes checker rides along with getters
    get_server: bool = True  # typed-BUSY overload bursts via KvQueryServer
    # CDC subscription storm (ISSUE 14): subscriber threads on one shared
    # decode-once hub, each folding its received changelog stream and
    # asserting fold == pinned-snapshot scan at its checkpoint; subscriber 0
    # is deliberately SLOW (must be shed with the typed protocol and resume
    # from its consumer-id), and an optional subscriber OS process rides
    # along, journaling batches, to be kill -9'd and respawned
    subscribers: int = 0
    slow_subscriber: bool = True
    # per-batch stall of the slow subscriber: decisively past the soak's
    # 1.5 s subscription.shed-timeout, so the shed fires whenever its queue
    # is full — independent of the host's commit rate
    sub_slow_sleep_s: float = 2.5
    sub_verify_every: int = 8  # fold==scan check cadence (batches)
    subscriber_procs: int = 0
    kill_subscriber: bool = True  # SIGKILL the subscriber process once
    # resilience (False = seed-like config: first fault aborts, no CAS retry)
    resilient: bool = True
    table_options: dict = field(default_factory=dict)

    @classmethod
    def from_table_options(cls, options) -> "SoakConfig":
        from ..options import CoreOptions

        o = options.options
        return cls(
            duration_s=o.get(CoreOptions.SOAK_DURATION) / 1000.0,
            writers=o.get(CoreOptions.SOAK_WRITERS),
            readers=o.get(CoreOptions.SOAK_READERS),
            fault_possibility=o.get(CoreOptions.SOAK_FAULT_POSSIBILITY),
            rows_per_commit=o.get(CoreOptions.SOAK_ROWS_PER_COMMIT),
            compact_every=o.get(CoreOptions.SOAK_COMPACT_EVERY),
        )


class SoakHarness:
    def __init__(self, base_dir: str, cfg: SoakConfig | None = None, domain: str | None = None):
        self.cfg = cfg or SoakConfig()
        self.base_dir = str(base_dir)
        self.domain = domain or f"soak{os.getpid()}_{self.cfg.seed}"
        self.local_root = os.path.join(self.base_dir, "soak_table")
        self.path = f"fail://{self.domain}{self.local_root}"
        self.stop = threading.Event()
        self.oracle = OracleLog()
        self.errors: list[str] = []  # unexpected thread crashes
        self.inconsistencies: list[dict] = []
        self.read_latencies_ms: list[float] = []
        self.get_latencies_us: list[float] = []  # per-key batched get latency
        self._lock = threading.Lock()
        self.counts = {
            "commits_ok": 0,
            "commits_failed": 0,
            "commits_conflict_survived": 0,  # raised, but APPEND landed
            "commits_conflict_aborted": 0,  # raised, nothing landed
            "writes_rejected_rounds": 0,
            "compactor_commits": 0,
            "compactor_conflicts": 0,
            "expire_runs": 0,
            "reads_ok": 0,
            "reads_expired_race": 0,
            "read_errors": 0,
            "gets_served": 0,  # probe keys answered by batched gets
            "get_rounds": 0,
            "get_oracle_checks": 0,
            "get_mismatches": 0,
            "gets_shed_typed": 0,  # KvBusyError responses under overload
            "gets_shed_untyped": 0,  # anything else (timeouts = failures)
            "ryw_rounds": 0,
            "ryw_misses": 0,
            "sub_batches": 0,  # ChangelogBatches received across subscribers
            "sub_rows": 0,
            "sub_verifies": 0,  # fold == pinned-scan checks performed
            "sub_mismatches": 0,
            "sub_shed_typed": 0,  # SubscriberShedError (slow consumer shed)
            "sub_shed_untyped": 0,  # anything else severing a subscriber
            "sub_resumes": 0,  # consumer-id resumes after a typed shed
            "subproc_kills": 0,  # SIGKILLs of the subscriber OS process
        }
        self._table = None
        self._controller = None
        self._sub_hub = None

    # ---- setup ---------------------------------------------------------
    def _table_options(self) -> dict:
        cfg = self.cfg
        opts = {
            "bucket": str(cfg.buckets),
            "merge.engine": "mesh" if cfg.mesh else "single",
            # small memtables force the offloaded-flush path under load
            "write-buffer-rows": str(max(cfg.write_chunk_rows * 2, 64)),
            # enough history that a pinned read never races expiry
            "snapshot.num-retained.min": "16",
            "snapshot.num-retained.max": "30",
            "commit.retry-backoff": "2 ms",
        }
        if cfg.subscribers or cfg.subscriber_procs:
            # subscription storm knobs: a shallow queue + short shed timeout
            # so the deliberately-slow subscriber actually gets shed, and a
            # fast heartbeat so durable progress (and the expiry pin) tracks
            # consumption closely
            opts.update(
                {
                    "subscription.queue-depth": "4",
                    "subscription.shed-timeout": "1500 ms",
                    "subscription.heartbeat-interval": "1 s",
                    "subscription.poll-backoff": "20 ms",
                }
            )
        if cfg.resilient:
            opts.update(
                {
                    "commit.max-retries": "30",
                    "fs.retry.max-attempts": "6",
                    "fs.retry.initial-backoff": "2 ms",
                    "fs.retry.max-backoff": "40 ms",
                }
            )
        else:
            # the seed contrast: first IO fault aborts, no CAS retry budget
            opts.update({"commit.max-retries": "0", "fs.retry.max-attempts": "1"})
        opts.update(cfg.table_options)
        return opts

    def setup(self):
        from ..core.schema import SchemaManager
        from ..fs import get_file_io
        from ..fs.testing import FailingFileIO
        from ..table import FileStoreTable

        FailingFileIO.reset(self.domain, 0, 0)
        io = get_file_io(self.path)
        ts = SchemaManager(io, self.path).create_table(
            SCHEMA, primary_keys=["k"], options=self._table_options()
        )
        self._table = FileStoreTable(io, self.path, ts, commit_user="soak-setup")
        if self.cfg.backpressure:
            from ..core.admission import WriteBufferController

            self._controller = WriteBufferController(
                self.cfg.max_memory,
                stop_trigger=self.cfg.stop_trigger,
                block_timeout_ms=self.cfg.block_timeout_ms,
                max_pending_flushes=self.cfg.max_pending_flushes,
            )
        if self.cfg.subscribers:
            # ONE hub: every subscriber thread rides the same decode-once
            # tailer (the subscriber process has its own, in its own process)
            from ..service.subscription import SubscriptionHub

            self._sub_hub = SubscriptionHub(self._table.with_user("soak-subhub"))
        return self._table

    def _handle(self, user: str):
        """A fresh table handle (own store, own commit user) — one per
        thread, exactly how independent jobs would mount the table."""
        return self._table.with_user(user)

    # ---- writer --------------------------------------------------------
    def _writer_loop(self, wid: int, deadline: float) -> None:
        from ..core.admission import WriterBackpressureError
        from ..core.commit import CommitConflictError, CommitGiveUpError
        from ..core.manifest import ManifestCommittable
        from ..fs.testing import ArtificialException
        from ..metrics import soak_metrics
        from ..table.write import TableWrite

        cfg = self.cfg
        rng = np.random.default_rng(cfg.seed * 7919 + wid)
        user = f"soak-w{wid}"
        table = self._handle(user)
        store = table.store
        g = soak_metrics()
        ident = 0
        next_key = 0
        written: list[int] = []
        while not self.stop.is_set() and time.monotonic() < deadline:
            ident += 1
            n_upd = int(cfg.rows_per_commit * cfg.update_fraction) if written else 0
            n_new = cfg.rows_per_commit - n_upd
            fresh = [wid * KEYSPACE + next_key + i for i in range(n_new)]
            upd = (
                [written[i] for i in rng.integers(0, len(written), n_upd)] if n_upd else []
            )
            keys = fresh + upd
            vals = (ident * 1_000.0 + wid) + rng.random(len(keys))
            rows = dict(zip(keys, [float(v) for v in vals]))  # unique keys per round
            try:
                tw = TableWrite(table, buffer_controller=self._controller)
                try:
                    data_keys = list(rows)
                    data_vals = [rows[k] for k in data_keys]
                    from ..data.batch import ColumnBatch

                    for i in range(0, len(data_keys), cfg.write_chunk_rows):
                        tw.write(
                            ColumnBatch.from_pydict(
                                SCHEMA,
                                {
                                    "k": data_keys[i : i + cfg.write_chunk_rows],
                                    "v": data_vals[i : i + cfg.write_chunk_rows],
                                },
                            )
                        )
                    if cfg.compact_every and ident % cfg.compact_every == 0:
                        tw.compact(full=True)
                    msgs = tw.prepare_commit()
                finally:
                    tw.close()  # releases any reservation this round still holds
                sids = store.new_commit().commit(ManifestCommittable(ident, messages=msgs))
                if sids:
                    self.oracle.record(sids[0], rows)
                    next_key += n_new
                    written.extend(fresh)
                    with self._lock:
                        self.counts["commits_ok"] += 1
                    g.counter("commits_ok").inc()
            except WriterBackpressureError:
                # load shed: the round was REJECTED before any byte buffered —
                # not lost, not accepted. Back off and continue.
                with self._lock:
                    self.counts["writes_rejected_rounds"] += 1
                time.sleep(0.02)
            except (CommitConflictError, CommitGiveUpError, ArtificialException):
                sid = find_landed_append(store, user, ident)
                if sid is not None:
                    # COMPACT half lost the race/faulted, APPEND landed: the
                    # rows ARE committed and the oracle must count them
                    self.oracle.record(sid, rows)
                    next_key += n_new
                    written.extend(fresh)
                    with self._lock:
                        self.counts["commits_conflict_survived"] += 1
                    g.counter("commits_conflict_replanned").inc()
                else:
                    with self._lock:
                        if self.cfg.resilient:
                            self.counts["commits_conflict_aborted"] += 1
                        else:
                            self.counts["commits_failed"] += 1

    # ---- reader --------------------------------------------------------
    def _append_sids_up_to(self, sm, sid: int) -> set[int]:
        """The soak-writer APPEND snapshots <= sid the oracle must cover
        before the read at sid can be judged. A snapshot that vanishes
        mid-walk was just expired — expiry only reaches OLD snapshots, whose
        commits were recorded long ago, so skipping it never weakens the
        coverage requirement (sm.snapshots() itself is list-then-read and
        would throw on exactly that race)."""
        from ..core.snapshot import CommitKind

        out: set[int] = set()
        earliest = sm.earliest_snapshot_id()
        if earliest is None:
            return out
        for i in range(earliest, sid + 1):
            try:
                if not sm.snapshot_exists(i):
                    continue
                snap = sm.snapshot(i)
            except FileNotFoundError:
                continue  # expired between the exists check and the read
            if snap.commit_kind == CommitKind.APPEND and snap.commit_user.startswith("soak-w"):
                out.add(snap.id)
        return out

    def _read_at(self, table, sid: int):
        t = table.copy({"scan.snapshot-id": str(sid)})
        rb = t.new_read_builder()
        splits = rb.new_scan().plan()
        return rb.new_read().read_all(splits)

    def _reader_loop(self, rid: int, deadline: float) -> None:
        user = f"soak-r{rid}"
        table = self._handle(user)
        sm = table.store.snapshot_manager
        while not self.stop.is_set() and time.monotonic() < deadline:
            t0 = time.perf_counter()
            try:
                sid = sm.latest_snapshot_id()
            except Exception:
                sid = None
            if sid is None:
                time.sleep(0.05)
                continue
            try:
                from ..fs.testing import ArtificialException

                try:
                    batch = self._read_at(table, sid)
                except ArtificialException:
                    # the IO layer already burned fs.retry.max-attempts; one
                    # fresh pass covers the (rare) full-budget exhaustion
                    batch = self._read_at(table, sid)
                needed = self._append_sids_up_to(sm, sid)
            except Exception as exc:
                earliest = None
                try:
                    earliest = sm.earliest_snapshot_id()
                except Exception:
                    pass
                with self._lock:
                    if earliest is not None and sid < earliest:
                        # pinned snapshot expired mid-read: a retriable race,
                        # not an inconsistency (retention bounds its rate)
                        self.counts["reads_expired_race"] += 1
                    else:
                        self.counts["read_errors"] += 1
                        self.errors.append(f"reader {rid} @ snapshot {sid}: {exc!r}")
                continue
            self.read_latencies_ms.append((time.perf_counter() - t0) * 1000)
            ks = batch.column("k").values.tolist()
            got = dict(zip(ks, batch.column("v").values.tolist()))
            if len(ks) != len(got):
                self.inconsistencies.append(
                    {"snapshot": sid, "kind": "duplicate-keys", "rows": len(ks), "unique": len(got)}
                )
                continue
            if not self.oracle.wait_covers(needed, timeout_s=10.0):
                self.inconsistencies.append(
                    {"snapshot": sid, "kind": "oracle-lag", "needed": sorted(needed)[-3:]}
                )
                continue
            expected = self.oracle.expected_at(sid)
            if got != expected:
                missing = [k for k in expected if k not in got]
                extra = [k for k in got if k not in expected]
                wrong = [k for k in expected if k in got and got[k] != expected[k]]
                self.inconsistencies.append(
                    {
                        "snapshot": sid,
                        "kind": "row-set-mismatch",
                        "missing": len(missing),
                        "extra": len(extra),
                        "wrong_value": len(wrong),
                        "sample": (missing[:3], extra[:3], wrong[:3]),
                    }
                )
            else:
                with self._lock:
                    self.counts["reads_ok"] += 1

    # ---- point-get storm (ISSUE 13) ------------------------------------
    RYW_WID = 97  # read-your-writes checker keyspace, disjoint from writers

    def _getter_loop(self, gid: int, deadline: float) -> None:
        """Batched point-gets against the live table: every round runs ONE
        vectorized get_batch over a random slice of a random writer's
        keyspace (present, absent and deleted keys all occur naturally),
        then verifies a random subset against the scalar lookup() walk —
        the independent oracle. Getter queries are private, so the levels
        they probe are frozen between their own refresh() calls."""
        from ..table.query import LocalTableQuery

        cfg = self.cfg
        rng = np.random.default_rng(cfg.seed * 104729 + gid)
        table = self._handle(f"soak-g{gid}")
        q = None
        while not self.stop.is_set() and time.monotonic() < deadline:
            try:
                if q is None:
                    q = LocalTableQuery(table)
                else:
                    q.refresh()
            except Exception:
                time.sleep(0.05)  # no snapshot yet / a refresh racing expiry
                continue
            wid = int(rng.integers(0, cfg.writers))
            keys = [
                int(wid * KEYSPACE + k)
                for k in rng.integers(0, 6000, size=cfg.get_batch_keys)
            ]
            t0 = time.perf_counter()
            try:
                got = q.get_batch(keys).to_pylist()
            except Exception as exc:
                with self._lock:
                    self.counts["read_errors"] += 1
                    self.errors.append(f"getter {gid}: {exc!r}")
                continue
            self.get_latencies_us.append(
                (time.perf_counter() - t0) / max(len(keys), 1) * 1e6
            )
            # scalar oracle on a random subset: the batched path and the
            # LookupLevels walk read the SAME frozen per-bucket state
            for i in rng.choice(len(keys), size=min(cfg.get_oracle_keys, len(keys)), replace=False):
                row = q.lookup((), keys[int(i)])
                expect = None if row is None else row.to_pylist()[0]
                with self._lock:
                    self.counts["get_oracle_checks"] += 1
                if got[int(i)] != expect:
                    with self._lock:
                        self.counts["get_mismatches"] += 1
                    self.inconsistencies.append(
                        {"kind": "get-mismatch", "key": keys[int(i)],
                         "batched": got[int(i)], "scalar": expect}
                    )
            with self._lock:
                self.counts["gets_served"] += len(keys)
                self.counts["get_rounds"] += 1

    def _get_overload_loop(self, deadline: float) -> None:
        """Deliberately overload a KvQueryServer (max_inflight_gets=1) with
        concurrent get_batch bursts: under saturation the server must answer
        a TYPED busy (KvBusyError with a retry hint) — a socket timeout or
        any other failure counts as untyped and fails the soak."""
        from ..service import KvBusyError, KvQueryClient, KvQueryServer

        try:
            server = KvQueryServer(self._table, max_inflight_gets=1)
            host, port = server.start()
        except Exception as exc:
            self.errors.append(f"get-overload server failed to start: {exc!r}")
            return
        try:
            clients = [KvQueryClient(host, port, timeout=30.0) for _ in range(4)]
            keys = [list(range(64))]

            def one(c):
                try:
                    c.get_batch(keys[0])
                    with self._lock:
                        self.counts["gets_served"] += len(keys[0])
                except KvBusyError:
                    with self._lock:
                        self.counts["gets_shed_typed"] += 1
                except Exception:
                    with self._lock:
                        self.counts["gets_shed_untyped"] += 1

            while not self.stop.is_set() and time.monotonic() < deadline:
                burst = [threading.Thread(target=one, args=(c,)) for c in clients]
                for t in burst:
                    t.start()
                for t in burst:
                    t.join(timeout=30.0)
                time.sleep(0.1)
            for c in clients:
                c.close()
        finally:
            server.shutdown()

    def _ryw_loop(self, deadline: float) -> None:
        """Read-your-writes checker: a committer on its own keyspace whose
        attached query must see every buffered row BEFORE the commit lands,
        and (after refresh) the committed rows after. Landed commits are
        recorded in the oracle exactly like writer commits, so the final
        verification covers this keyspace too."""
        from ..core.commit import CommitConflictError, CommitGiveUpError
        from ..core.manifest import ManifestCommittable
        from ..data.batch import ColumnBatch
        from ..fs.testing import ArtificialException
        from ..table.query import LocalTableQuery
        from ..table.write import TableWrite

        cfg = self.cfg
        rng = np.random.default_rng(cfg.seed * 7919 + 9999)
        user = "soak-ryw"
        table = self._handle(user)
        store = table.store
        tw = None
        q = None
        ident = 0
        next_key = 0
        while not self.stop.is_set() and time.monotonic() < deadline:
            ident += 1
            keys = [self.RYW_WID * KEYSPACE + next_key + i for i in range(32)]
            vals = [float(ident * 1000 + i) + float(rng.random()) for i in range(32)]
            rows = dict(zip(keys, vals))
            try:
                if tw is None:
                    tw = TableWrite(table, buffer_controller=self._controller)
                    q = None
                if q is None:
                    q = LocalTableQuery(table).attach_write(tw)
                else:
                    q.refresh()
                tw.write(ColumnBatch.from_pydict(SCHEMA, {"k": keys, "v": vals}))
                got = q.get_batch(keys).to_pylist()
                with self._lock:
                    self.counts["ryw_rounds"] += 1
                misses = [
                    k for k, g in zip(keys, got) if g is None or g[1] != rows[k]
                ]
                if misses:
                    with self._lock:
                        self.counts["ryw_misses"] += len(misses)
                    self.inconsistencies.append(
                        {"kind": "ryw-miss", "ident": ident, "missing": misses[:3]}
                    )
                msgs = tw.prepare_commit()
                sids = store.new_commit().commit(ManifestCommittable(ident, messages=msgs))
                if sids:
                    self.oracle.record(sids[0], rows)
                    next_key += 32
            except (CommitConflictError, CommitGiveUpError, ArtificialException):
                sid = find_landed_append(store, user, ident)
                if sid is not None:
                    self.oracle.record(sid, rows)
                    next_key += 32
                    with self._lock:
                        self.counts["commits_conflict_survived"] += 1
                else:
                    with self._lock:
                        self.counts["commits_conflict_aborted"] += 1
                # a failed round may leave writer state ambiguous: rebuild
                try:
                    tw.close()
                except Exception:
                    pass
                tw = None
            except Exception as exc:
                with self._lock:
                    self.errors.append(f"ryw checker: {exc!r}")
                try:
                    tw.close()
                except Exception:
                    pass
                tw = None
        if tw is not None:
            try:
                tw.close()
            except Exception:
                pass

    # ---- CDC subscribers (ISSUE 14) ------------------------------------
    def _sub_scan_at(self, table, sid: int):
        """Pinned scan at sid as {key: full row tuple} — the truth a
        subscriber's fold is checked against (one retry for the rare
        full-retry-budget fault exhaustion, like the reader loop)."""
        from ..fs.testing import ArtificialException

        try:
            batch = self._read_at(table, sid)
        except ArtificialException:
            batch = self._read_at(table, sid)
        ks = batch.column("k").values.tolist()
        vs = batch.column("v").values.tolist()
        return {(k,): (k, v) for k, v in zip(ks, vs)}

    def _subscriber_loop(self, sidx: int, deadline: float) -> None:
        """One subscriber on the shared decode-once hub: fold every received
        batch (sid-deduped, so at-least-once replays after a shed/resume are
        harmless) and periodically assert fold == pinned scan at the
        checkpoint. Subscriber 0 (slow_subscriber) stalls per batch until the
        hub sheds it with the typed protocol, then resumes from its
        consumer-id — losslessly."""
        from ..service.subscription import SubscriberShedError

        cfg = self.cfg
        slow = cfg.slow_subscriber and sidx == 0
        table = self._handle(f"soak-sub{sidx}")
        consumer = f"soak-sub-{sidx}"
        received: dict[int, object] = {}  # sid -> ChangelogBatch (last wins)

        def fold_up_to(sid: int) -> dict:
            from ..service.subscription import fold_changelog

            state: dict = {}
            for s in sorted(received):
                if s <= sid:
                    fold_changelog(state, received[s], ["k"])
            return state

        def verify(sid: int) -> None:
            with self._lock:
                self.counts["sub_verifies"] += 1
            expected = self._sub_scan_at(table, sid)
            got = fold_up_to(sid)
            if got != expected:
                with self._lock:
                    self.counts["sub_mismatches"] += 1
                missing = [k for k in expected if k not in got]
                extra = [k for k in got if k not in expected]
                self.inconsistencies.append(
                    {
                        "kind": "sub-fold-mismatch",
                        "subscriber": sidx,
                        "snapshot": sid,
                        "missing": len(missing),
                        "extra": len(extra),
                        "sample": (missing[:3], extra[:3]),
                    }
                )

        sub = None
        since_verify = 0
        try:
            while not self.stop.is_set():
                draining = time.monotonic() >= deadline
                try:
                    if sub is None:
                        sub = self._sub_hub.subscribe(consumer_id=consumer, from_snapshot=1)
                    batch = sub.poll(timeout=1.0)
                except SubscriberShedError:
                    with self._lock:
                        self.counts["sub_shed_typed"] += 1
                        self.counts["sub_resumes"] += 1
                    sub = None  # resume from the durable consumer position
                    continue
                except Exception as exc:
                    if draining:
                        break
                    with self._lock:
                        self.counts["sub_shed_untyped"] += 1
                        self.errors.append(f"subscriber {sidx}: {exc!r}")
                    time.sleep(0.2)
                    continue
                if batch is None:
                    if draining:
                        break  # queue drained after the writer deadline
                    continue
                received[batch.snapshot_id] = batch
                since_verify += 1
                with self._lock:
                    self.counts["sub_batches"] += 1
                    self.counts["sub_rows"] += batch.num_rows
                if slow and not draining:
                    time.sleep(cfg.sub_slow_sleep_s)
                if since_verify >= cfg.sub_verify_every and not draining:
                    since_verify = 0
                    try:
                        verify(batch.snapshot_id)
                    except Exception as exc:
                        with self._lock:
                            self.errors.append(f"subscriber {sidx} verify @ {batch.snapshot_id}: {exc!r}")
            # final oracle: the fold of EVERYTHING received must equal the
            # pinned scan at the final checkpoint, for every subscriber
            if received:
                try:
                    verify(max(received))
                except Exception as exc:
                    with self._lock:
                        self.errors.append(f"subscriber {sidx} final verify: {exc!r}")
        finally:
            if sub is not None:
                try:
                    sub.close()
                except Exception:
                    pass

    def _subscriber_proc_loop(self, deadline: float) -> None:
        """Subscriber as an OS process (the kill -9 half of the oracle): a
        child subscribes with a durable consumer-id and journals every batch
        (fsync per line). Mid-soak the supervisor SIGKILLs it and respawns
        it with the SAME consumer-id; the respawn resumes from the recorded
        position. _verify folds the journal (sid-deduped) and asserts it
        equals the pinned scan at the journal's checkpoint."""
        import signal
        import subprocess
        import sys

        cfg = self.cfg
        self._subproc_journal = os.path.join(self.base_dir, "subscriber_proc.journal")
        consumer = "soak-subproc"

        def spawn() -> subprocess.Popen:
            remaining = max(deadline - time.monotonic(), 1.0)
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"  # the parent may hold the chip
            return subprocess.Popen(
                [
                    sys.executable,
                    "-m",
                    "paimon_tpu.service.subscription",
                    "--table",
                    self.path,
                    "--consumer",
                    consumer,
                    "--journal",
                    self._subproc_journal,
                    "--duration",
                    str(remaining + 5.0),
                    "--from-snapshot",
                    "1",
                ],
                env=env,
            )

        proc = spawn()
        kill_at = time.monotonic() + max((deadline - time.monotonic()) * 0.45, 2.0)
        killed = False
        try:
            while time.monotonic() < deadline and not self.stop.is_set():
                if cfg.kill_subscriber and not killed and time.monotonic() >= kill_at:
                    try:
                        os.kill(proc.pid, signal.SIGKILL)
                        proc.wait(timeout=30)
                    except Exception:
                        pass
                    killed = True
                    with self._lock:
                        self.counts["subproc_kills"] += 1
                    proc = spawn()  # same consumer-id: durable resume
                if proc.poll() is not None and time.monotonic() < deadline - 3.0:
                    # premature death is a failure unless we just killed it
                    with self._lock:
                        self.errors.append(
                            f"subscriber process exited early rc={proc.returncode}"
                        )
                    return
                time.sleep(0.2)
            try:
                proc.wait(timeout=60 + cfg.duration_s)
            except Exception:
                proc.kill()
                with self._lock:
                    self.errors.append("subscriber process failed to drain; killed")
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)

    def _verify_subproc_journal(self) -> None:
        """Fold the subscriber process's journal and assert it equals the
        pinned-snapshot scan at its checkpoint — across the kill -9."""
        import json as _json

        path = getattr(self, "_subproc_journal", None)
        if path is None or not os.path.exists(path):
            self.errors.append("subscriber process journal missing")
            return
        from ..types import RowKind

        by_sid: dict[int, tuple[list, list]] = {}
        done = None
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = _json.loads(line)
                except ValueError:
                    continue  # torn tail from the SIGKILL
                if "sid" in rec:
                    by_sid[rec["sid"]] = (rec["rows"], rec["kinds"])
                elif rec.get("done"):
                    done = rec.get("checkpoint")
        if not by_sid:
            self.errors.append("subscriber process journal recorded no batches")
            return
        checkpoint = max(by_sid)
        state: dict = {}
        for sid in sorted(by_sid):
            rows, kinds = by_sid[sid]
            for row, kind in zip(rows, kinds):
                k = RowKind(int(kind))
                if k in (RowKind.INSERT, RowKind.UPDATE_AFTER):
                    state[(row[0],)] = tuple(row)
                elif k == RowKind.DELETE:
                    state.pop((row[0],), None)
        table = self._handle("soak-subproc-verify")
        expected = self._sub_scan_at(table, checkpoint)
        self.counts["sub_verifies"] += 1
        if state != expected:
            self.counts["sub_mismatches"] += 1
            missing = [k for k in expected if k not in state]
            extra = [k for k in state if k not in expected]
            self.inconsistencies.append(
                {
                    "kind": "subproc-journal-mismatch",
                    "checkpoint": checkpoint,
                    "done_marker": done,
                    "missing": len(missing),
                    "extra": len(extra),
                    "sample": (missing[:3], extra[:3]),
                }
            )

    # ---- churn ---------------------------------------------------------
    def _compactor_loop(self, deadline: float) -> None:
        from ..core.commit import BATCH_COMMIT_IDENTIFIER, CommitConflictError, CommitGiveUpError
        from ..core.manifest import ManifestCommittable
        from ..fs.testing import ArtificialException
        from ..table.write import TableWrite

        table = self._handle("soak-compactor")
        store = table.store
        if self.cfg.adaptive:
            # adaptive churn: the LUDA scheduler observes per-bucket LSM
            # shape each round and compacts by heat/read-amp priority —
            # run_round() is driven from this thread (no service thread),
            # so drain/join semantics stay identical to the legacy loop
            from ..table.compactor import AdaptiveCompactorService

            svc = AdaptiveCompactorService(table)
            while not self.stop.is_set() and time.monotonic() < deadline:
                time.sleep(self.cfg.compactor_pause_s)
                try:
                    done = svc.run_round()
                    if done:
                        with self._lock:
                            self.counts["compactor_commits"] += done
                except (CommitConflictError, CommitGiveUpError, ArtificialException):
                    # a fault mid-observation/compaction aborts the round;
                    # rows are untouched — writers own them
                    with self._lock:
                        self.counts["compactor_conflicts"] += 1
            return
        while not self.stop.is_set() and time.monotonic() < deadline:
            time.sleep(self.cfg.compactor_pause_s)
            try:
                tw = TableWrite(table)
                try:
                    tw.compact(full=True)
                    msgs = tw.prepare_commit()
                finally:
                    tw.close()
                if not msgs:
                    continue
                store.new_commit().commit(ManifestCommittable(BATCH_COMMIT_IDENTIFIER, messages=msgs))
                with self._lock:
                    self.counts["compactor_commits"] += 1
            except (CommitConflictError, CommitGiveUpError, ArtificialException):
                # losing a compaction race (or a fault aborting one) is the
                # expected storm; rows are untouched — writers own them
                with self._lock:
                    self.counts["compactor_conflicts"] += 1

    def _expirer_loop(self, deadline: float) -> None:
        table = self._handle("soak-expirer")
        while not self.stop.is_set() and time.monotonic() < deadline:
            time.sleep(self.cfg.expire_every_s)
            try:
                table.expire_snapshots()
                with self._lock:
                    self.counts["expire_runs"] += 1
            except Exception:
                pass  # expiry is maintenance: faults here must never matter

    # ---- orchestration -------------------------------------------------
    def _spawn(self, name: str, fn, *args) -> threading.Thread:
        def guarded():
            try:
                fn(*args)
            except BaseException:
                self.errors.append(f"{name} crashed:\n{traceback.format_exc()}")

        t = threading.Thread(target=guarded, name=name, daemon=False)
        t.start()
        return t

    def run(self) -> dict:
        from ..fs.testing import FailingFileIO
        from ..metrics import registry, soak_metrics

        cfg = self.cfg
        if self._table is None:
            self.setup()
        # drop ONLY the soak{...} group so back-to-back runs in one process
        # (the bench's full-vs-seed contrast) report their own counters;
        # other groups keep accumulating and are reported as deltas
        with registry._lock:
            registry.groups.pop(("soak", ()), None)
        commit_group = registry.group("commit")
        base_retries = commit_group.counter("retries").count
        base_abandoned = commit_group.counter("buckets_abandoned").count
        base_conflicts = commit_group.counter("conflicts").count
        if cfg.fault_possibility > 0:
            FailingFileIO.reset(
                self.domain, max_fails=10**9, possibility=cfg.fault_possibility, seed=cfg.seed
            )
        t_start = time.monotonic()
        deadline = t_start + cfg.duration_s
        threads = [
            self._spawn(f"soak-writer-{w}", self._writer_loop, w, deadline)
            for w in range(cfg.writers)
        ]
        threads += [
            self._spawn(f"soak-reader-{r}", self._reader_loop, r, deadline)
            for r in range(cfg.readers)
        ]
        threads += [
            self._spawn(f"soak-getter-{g}", self._getter_loop, g, deadline)
            for g in range(cfg.getters)
        ]
        if cfg.getters and cfg.ryw:
            threads.append(self._spawn("soak-ryw", self._ryw_loop, deadline))
        if cfg.getters and cfg.get_server:
            threads.append(self._spawn("soak-get-overload", self._get_overload_loop, deadline))
        threads += [
            self._spawn(f"soak-sub-{s}", self._subscriber_loop, s, deadline)
            for s in range(cfg.subscribers)
        ]
        if cfg.subscriber_procs:
            threads.append(self._spawn("soak-subproc-super", self._subscriber_proc_loop, deadline))
        threads.append(self._spawn("soak-compactor", self._compactor_loop, deadline))
        threads.append(self._spawn("soak-expirer", self._expirer_loop, deadline))
        for t in threads:
            t.join(timeout=cfg.duration_s + max(120.0, cfg.block_timeout_ms / 1000.0 * 3))
        alive = [t.name for t in threads if t.is_alive()]
        if alive:
            self.stop.set()
            for t in threads:
                t.join(timeout=60.0)
            self.errors.append(f"threads failed to drain in time: {alive}")
        if self._sub_hub is not None:
            self._sub_hub.close()
        wall_s = time.monotonic() - t_start
        FailingFileIO.reset(self.domain, 0, 0)  # faults off for verification
        report = self._verify(wall_s)
        g = soak_metrics()
        g.counter("commits_retried").inc(commit_group.counter("retries").count - base_retries)
        report["commit_cas_retries"] = commit_group.counter("retries").count - base_retries
        report["commit_conflicts_detected"] = commit_group.counter("conflicts").count - base_conflicts
        report["commit_buckets_replanned"] = (
            commit_group.counter("buckets_abandoned").count - base_abandoned
        )
        if self.read_latencies_ms:
            p50 = float(np.percentile(self.read_latencies_ms, 50))
            p99 = float(np.percentile(self.read_latencies_ms, 99))
            g.gauge("read_p50_ms").set(p50)
            g.gauge("read_p99_ms").set(p99)
            report["read_p50_ms"] = round(p50, 2)
            report["read_p99_ms"] = round(p99, 2)
        else:
            report["read_p50_ms"] = report["read_p99_ms"] = None
        report["gets_per_sec"] = (
            round(self.counts["gets_served"] / wall_s, 1) if wall_s > 0 else None
        )
        if self.get_latencies_us:
            from ..metrics import get_metrics

            p99_us = float(np.percentile(self.get_latencies_us, 99))
            get_metrics().gauge("p99_us").set(p99_us)
            report["get_p50_us"] = round(float(np.percentile(self.get_latencies_us, 50)), 2)
            report["get_p99_us"] = round(p99_us, 2)
        else:
            report["get_p50_us"] = report["get_p99_us"] = None
        return report

    # ---- post-soak verification ----------------------------------------
    def _verify(self, wall_s: float) -> dict:
        from .oracle import verify_table_state

        expected = self.oracle.expected_final()
        state = verify_table_state(
            self._handle("soak-verify"),
            expected,
            self.local_root,
            self.errors,
            self.inconsistencies,
        )
        from ..metrics import soak_metrics

        g = soak_metrics()
        if self.cfg.subscriber_procs:
            try:
                self._verify_subproc_journal()
            except Exception:
                self.errors.append(f"subproc journal verification crashed:\n{traceback.format_exc()}")
        consistent = (
            not self.inconsistencies
            and not self.errors
            and state["lost_rows"] == 0
            and state["duplicated_rows"] == 0
            and state["wrong_values"] == 0
            and self.counts["gets_shed_untyped"] == 0  # overload must shed TYPED
            and self.counts["sub_shed_untyped"] == 0  # slow consumers shed TYPED
            and self.counts["sub_mismatches"] == 0  # every fold == pinned scan
            and state["record_count_matches"]
        )
        report = {
            "wall_s": round(wall_s, 2),
            "consistent": consistent,
            "accepted_commits": self.oracle.commits,
            "accepted_rows": self.oracle.accepted_rows,
            "expected_unique_keys": len(expected),
            "final_rows": state["final_rows"],
            "total_record_count": state["total_record_count"],
            "lost_rows": state["lost_rows"],
            "duplicated_rows": state["duplicated_rows"],
            "wrong_values": state["wrong_values"],
            "commits_per_sec": round(self.oracle.commits / wall_s, 2) if wall_s > 0 else None,
            "writes_throttled": g.counter("writes_throttled").count,
            "writes_rejected": g.counter("writes_rejected").count,
            "backpressure_ms_mean": round(g.histogram("backpressure_ms").mean, 2),
            "inconsistencies": self.inconsistencies[:10],
            "errors": self.errors[:5],
            **self.counts,
            "orphans_removed": state["orphans_removed"],
            "leaked_files": state["leaked_files"][:10],
            "leaked_file_count": len(state["leaked_files"]),
        }
        return report


def run_soak(base_dir: str, cfg: SoakConfig | None = None, domain: str | None = None) -> dict:
    """Create a fresh soak table under base_dir, run the harness, return the
    report dict (see SoakHarness._verify for fields)."""
    return SoakHarness(base_dir, cfg, domain=domain).run()


def main(argv: list[str] | None = None) -> int:
    import argparse
    import json
    import tempfile

    ap = argparse.ArgumentParser(description="paimon-tpu production traffic soak")
    ap.add_argument("base_dir", nargs="?", default=None)
    ap.add_argument("--duration", type=float, default=45.0)
    ap.add_argument("--writers", type=int, default=3)
    ap.add_argument("--readers", type=int, default=2)
    ap.add_argument("--getters", type=int, default=0, help="batched point-get storm threads")
    ap.add_argument("--subscribers", type=int, default=0, help="CDC subscription storm threads")
    ap.add_argument("--subscriber-procs", type=int, default=0, help="subscriber OS processes (kill -9 + resume)")
    ap.add_argument("--fault-possibility", type=int, default=20, help="1/N ops fail (20 = 5%%)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", action="store_true")
    ap.add_argument("--adaptive", action="store_true", help="adaptive (LUDA) churn compactor")
    ap.add_argument("--no-backpressure", action="store_true")
    ap.add_argument("--seed-mode", action="store_true", help="seed-like resilience: no IO/CAS retries")
    args = ap.parse_args(argv)
    base = args.base_dir or tempfile.mkdtemp(prefix="paimon_soak_")
    cfg = SoakConfig(
        duration_s=args.duration,
        writers=args.writers,
        readers=args.readers,
        getters=args.getters,
        subscribers=args.subscribers,
        subscriber_procs=args.subscriber_procs,
        fault_possibility=args.fault_possibility,
        seed=args.seed,
        mesh=args.mesh,
        adaptive=args.adaptive or os.environ.get("PAIMON_TPU_SOAK_ADAPTIVE", "") == "1",
        backpressure=not args.no_backpressure,
        resilient=not args.seed_mode,
    )
    report = run_soak(base, cfg)
    print(json.dumps(report, indent=2, default=str))
    return 0 if report["consistent"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
