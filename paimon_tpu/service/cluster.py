"""Cluster service: coordinator/worker mesh execution with a cluster-wide
compaction drain.

The reference scales out by running many buckets across Flink/Spark task
managers while a SINGLE-parallelism committer serializes snapshots (SURVEY
§2.9). This module joins the two halves this repo already built separately:
the mesh engine (PR 7: many devices, ONE process) and the proc-soak
supervisor (PR 9: many processes, NO devices).

  coordinator (this process — the only committer)
  ├── bucket assignment: contiguous ranges, per-bucket epochs, reassignment
  │   on missed heartbeats (exactly once per orphaned bucket)
  ├── per-worker commit handles: workers ship CommitMessages, the
  │   coordinator commits through the snapshot-CAS path
  │   (parallel.distributed.is_commit_coordinator — the reference's
  │   CommitterOperator)
  ├── cluster compaction service: table.compactor.AdaptiveCompactorService
  │   observing + deciding here, with execute_group plugged so each decision
  │   dispatches to the worker OWNING that bucket; the worker rewrites
  │   through its local mesh engine and ships the result back; the
  │   debt-admission gate (read-amp ceiling) is enforced cluster-wide via
  │   the admit RPC, charges tagged per worker (a killed worker's charges
  │   release on reassignment)
  ├── worker-0 (OS process): jax runtime with N forced-host devices,
  │   merge.engine=mesh over its bucket shard, intent/ack journal (PR 9),
  │   serving plane (get_batch + subscribe + join_part) on its own port
  ├── worker-1 ...
  └── reader processes (reused from proc_soak) pinning + scanning snapshots

Correctness fences:
  * epoch fencing — every (re)grant of a bucket bumps its epoch; a shipped
    CommitMessage is rejected as STALE unless every touched bucket is still
    owned by the shipper at an epoch <= the one it shipped with. A worker
    killed, reassigned, and then heard from again cannot double-apply.
  * journal/oracle — the PR 9 protocol verbatim: intent fsynced before the
    ship, ack after the coordinator's sid comes back, landed-unacked rounds
    resolved from the snapshot chain on respawn (adopt-never-replay).
  * debt gate — admit() charges the coordinator's AdaptiveCompactorService
    projection per target bucket (owner-tagged); ship/abort settles, death
    releases. No bucket's projected sorted-run count passes the ceiling.

Run directly:  python -m paimon_tpu.service.cluster [base_dir] [flags]
Child roles:   python -m paimon_tpu.service.cluster worker|reader ...
"""

from __future__ import annotations

import base64
import json
import os
import socket
import socketserver
import subprocess
import sys
import threading
import time
import traceback
from dataclasses import dataclass, field

import numpy as np

from . import _recv, _send
from .soak import KEYSPACE, SCHEMA, find_landed_append

__all__ = [
    "ClusterConfig",
    "ClusterCoordinator",
    "ClusterWorkerAgent",
    "ClusterClient",
    "ClusterSupervisor",
    "run_cluster_soak",
    "DEFAULT_CLUSTER_KILLS",
]

# one spec per worker spawn while they last: one ingest-flush death, one
# mid-compaction death (the rewrite ran, the CommitMessage never shipped —
# its debt charge and its bucket range must both be recovered), one death
# between prepare_commit and the ship RPC
DEFAULT_CLUSTER_KILLS = (
    "flush:files-written:2:kill",
    "cluster:compact-executing:1:kill",
    "cluster:before-ship:2:kill",
)


def _b64(arr: np.ndarray) -> dict:
    a = np.ascontiguousarray(arr)
    return {"d": base64.b64encode(a.tobytes()).decode(), "t": str(a.dtype), "s": list(a.shape)}


def _unb64(d: dict) -> np.ndarray:
    return np.frombuffer(base64.b64decode(d["d"]), dtype=np.dtype(d["t"])).reshape(d["s"])


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------
@dataclass
class ClusterConfig:
    workers: int = 2
    devices_per_worker: int = 2
    buckets: int = 4
    duration_s: float = 45.0
    seed: int = 0
    round_rows: int = 256  # per owned bucket per ingest round
    update_fraction: float = 0.3
    heartbeat_interval_s: float = 0.5
    heartbeat_timeout_s: float = 4.0
    admit_timeout_s: float = 30.0
    compaction: bool = True
    read_amp_ceiling: int = 10
    readers: int = 1
    scripted_kills: tuple = DEFAULT_CLUSTER_KILLS
    kill_period_s: float = 10.0  # mean seconds between random SIGKILLs (0 = scripted only)
    sweep_period_s: float = 15.0
    sweep_older_than_ms: int = 45_000
    serve: bool = True  # workers run the get/subscribe serving plane
    # scripted elastic events: ("rescale", frac, new_buckets) /
    # ("admit", frac) / ("retire", frac) — frac is the fraction of
    # duration_s at which the event fires (the elastic soak's churn plan)
    elastic: tuple = ()
    table_options: dict = field(default_factory=dict)

    @classmethod
    def from_table_options(cls, options) -> "ClusterConfig":
        from ..options import CoreOptions

        o = options.options
        return cls(
            workers=o.get(CoreOptions.CLUSTER_WORKERS),
            devices_per_worker=o.get(CoreOptions.CLUSTER_DEVICES_PER_WORKER),
            heartbeat_interval_s=o.get(CoreOptions.CLUSTER_HEARTBEAT_INTERVAL) / 1000.0,
            heartbeat_timeout_s=o.get(CoreOptions.CLUSTER_HEARTBEAT_TIMEOUT) / 1000.0,
            round_rows=o.get(CoreOptions.CLUSTER_ROUND_ROWS),
            admit_timeout_s=o.get(CoreOptions.CLUSTER_ADMIT_TIMEOUT) / 1000.0,
            compaction=o.get(CoreOptions.CLUSTER_COMPACTION_ENABLED),
        )


# ---------------------------------------------------------------------------
# coordinator
# ---------------------------------------------------------------------------
class _WorkerSlot:
    def __init__(self, wid: int):
        self.wid = wid
        self.incarnation = -1
        self.buckets: set[int] = set()
        self.epoch = 0  # assignment epoch the worker was last told
        self.last_heartbeat = time.monotonic()
        self.alive = False
        self.serve_addr: tuple[str, int] | None = None
        self.tasks: list[dict] = []  # queued compaction tasks
        self.committed: dict[int, int] = {}  # ident -> sid (idempotent re-ship)
        self.done_stats: dict | None = None


class ClusterCoordinator:
    """Assignment + commit + compaction-scheduling brain, fronted by a
    threaded length-prefixed-JSON TCP server (the KvQueryServer protocol).
    All state transitions happen in handle() under one lock, so tests drive
    the failover edges directly without sockets."""

    USER_PREFIX = "cluster-w"

    def __init__(
        self,
        table_path: str,
        cfg: ClusterConfig,
        host: str = "127.0.0.1",
        port: int = 0,
    ):
        from ..table import load_table

        # the one committer: everything in this process commits, nothing in
        # any worker does (parallel.distributed.is_commit_coordinator)
        os.environ.setdefault("PAIMON_TPU_CLUSTER_ROLE", "coordinator")
        self.cfg = cfg
        self.table_path = table_path
        self.table = load_table(table_path, commit_user="cluster-coordinator")
        self.num_buckets = max(self.table.store.options.bucket, 1)
        self._lock = threading.RLock()
        self._slots: dict[int, _WorkerSlot] = {}
        self._owner: dict[int, int] = {}  # bucket -> wid
        self._bucket_epoch: dict[int, int] = {}  # bucket -> epoch of last grant
        self._epoch = 0
        self._pending: list[int] = []  # orphaned buckets with no live worker
        self._home: dict[int, list[int]] = self._split_ranges()
        self._commit_stores: dict[int, object] = {}
        self._admit_charges: dict[tuple, list[int]] = {}  # (wid, ident) -> buckets
        self._compact_inflight: dict[tuple, tuple] = {}  # (part, bucket) -> (task_id, wid)
        self._task_seq = 0
        self._task_groups: dict[int, list] = {}  # task_id -> [CompactionDecision]
        self._barriers: dict[str, set[int]] = {}
        # elastic topology (ISSUE 19): the route epoch bumps on ANY
        # reassignment / rescale / replica change and piggybacks on every
        # RPC reply (coordinator and worker serving planes alike), so
        # clients refresh the bucket->worker table immediately instead of
        # discovering staleness via a rejected shipment or a timeout window
        self._route_epoch = 1
        self._rescale: dict | None = None  # active cross-worker rescale state
        self._rescale_committing = False
        self._retiring: set[int] = set()  # wids told to drain + hand off
        self._replicas: dict[int, list[int]] = {}  # bucket -> replica wids
        self._get_counts: dict[int, int] = {}  # bucket -> gets since last pass
        self._heat_ema: dict[int, float] = {}  # bucket -> serve-read EMA (1/s)
        self._heat_t: float | None = None
        self._next_replica_pass = 0.0
        from ..options import CoreOptions

        o = self.table.store.options.options
        self.replica_threshold = float(o.get(CoreOptions.CLUSTER_REPLICA_HEAT_THRESHOLD))
        self.replica_max = int(o.get(CoreOptions.CLUSTER_REPLICA_MAX_PER_BUCKET))
        self.replica_interval_s = o.get(CoreOptions.CLUSTER_REPLICA_INTERVAL) / 1000.0
        self.rescale_timeout_s = o.get(CoreOptions.CLUSTER_RESCALE_TIMEOUT) / 1000.0
        self.go_event = threading.Event()
        self.stop_event = threading.Event()
        self.compaction = None
        if cfg.compaction:
            from ..table.compactor import AdaptiveCompactorService

            self.compaction = AdaptiveCompactorService(
                self.table, execute_group=self._dispatch_group
            )
        # TCP front
        outer = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                while True:
                    req = _recv(self.request)
                    if req is None:
                        return
                    rid = req.pop("id", None)
                    method = req.pop("method", "")
                    try:
                        out = outer.handle(method, req)
                        out["id"] = rid
                        out.setdefault("ok", True)
                    except Exception as e:  # noqa: BLE001 — surface to the worker
                        out = {"id": rid, "ok": False, "error": f"{type(e).__name__}: {e}"}
                    _send(self.request, out)

        self._server = socketserver.ThreadingTCPServer((host, port), Handler, bind_and_activate=True)
        self._server.daemon_threads = True
        self.host, self.port = self._server.server_address[0], self._server.server_address[1]
        self._threads: list[threading.Thread] = []

    # ---- lifecycle -----------------------------------------------------
    def start(self) -> "ClusterCoordinator":
        t = threading.Thread(target=self._server.serve_forever, daemon=True)
        t.start()
        self._threads.append(t)
        reaper = threading.Thread(
            target=self._reap_loop, name="paimon-clu-reaper", daemon=True
        )
        reaper.start()
        self._threads.append(reaper)
        if self.compaction is not None:
            self.compaction.start()
        return self

    def close(self) -> None:
        self.stop_event.set()
        if self.compaction is not None:
            self.compaction.close()
        self._server.shutdown()
        self._server.server_close()

    def __enter__(self) -> "ClusterCoordinator":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    # ---- assignment ----------------------------------------------------
    def _split_ranges(self) -> dict[int, list[int]]:
        """Home ranges: contiguous, disjoint, covering [0, num_buckets)."""
        n, w = self.num_buckets, max(self.cfg.workers, 1)
        out: dict[int, list[int]] = {}
        for i in range(w):
            out[i] = list(range(i * n // w, (i + 1) * n // w))
        return out

    def _metrics(self):
        from ..metrics import cluster_metrics

        return cluster_metrics()

    def _grant(self, slot: _WorkerSlot, buckets: list[int]) -> None:
        """Move `buckets` to `slot` under the lock, bumping the fence."""
        self._epoch += 1
        for b in buckets:
            prev = self._owner.get(b)
            if prev is not None and prev != slot.wid:
                self._slots[prev].buckets.discard(b)
            self._owner[b] = slot.wid
            self._bucket_epoch[b] = self._epoch
            slot.buckets.add(b)
            if b in self._pending:
                self._pending.remove(b)
        slot.epoch = self._epoch
        self._route_epoch += 1
        # a grant DURING a rescale re-queues the rewrite for any moved
        # bucket not yet done — the new owner's task carries the post-grant
        # epoch, so the dead previous owner's late rescale shipment for the
        # same bucket is fenced off exactly like a late append
        if self._rescale is not None:
            todo = [b for b in buckets if b not in self._rescale["done"]]
            if todo:
                slot.tasks.append(self._rescale_task(todo))

    def _rescale_task(self, buckets: list[int]) -> dict:
        rs = self._rescale
        return {
            "kind": "rescale",
            "buckets": sorted(buckets),
            "new_buckets": rs["new"],
            "snapshot": rs["snapshot"],
            "epoch": self._epoch,
        }

    def _reassign_dead(self, slot: _WorkerSlot) -> None:
        """Missed-heartbeat death: every bucket the dead worker owned moves
        EXACTLY ONCE to a live worker (least-loaded first), or parks in the
        pending list until one registers; the worker's queued compaction
        tasks, in-flight compaction marks, and debt-gate charges all
        release (nothing it never shipped can ever land)."""
        g = self._metrics()
        slot.alive = False
        orphans = sorted(slot.buckets)
        slot.buckets.clear()
        slot.tasks.clear()
        for key, (task_id, wid) in list(self._compact_inflight.items()):
            if wid == slot.wid:
                del self._compact_inflight[key]
                self._task_groups.pop(task_id, None)
        # release the dead worker's debt-gate charges (ingest admits it
        # never shipped + compaction decisions it never completed)
        released = 0
        for (wid, ident), buckets in list(self._admit_charges.items()):
            if wid == slot.wid:
                del self._admit_charges[(wid, ident)]
        if self.compaction is not None:
            released = self.compaction.release_owner(slot.wid)
        if released:
            g.counter("charges_released").inc(released)
        # drop the dead worker from every replica set before choosing new
        # owners, so promotion below never picks the corpse
        pruned = False
        for b, wids in list(self._replicas.items()):
            if slot.wid in wids:
                wids = [w for w in wids if w != slot.wid]
                pruned = True
                if wids:
                    self._replicas[b] = wids
                else:
                    del self._replicas[b]
        live = [s for s in self._slots.values() if s.alive]
        if not live:
            self._pending.extend(orphans)
        else:
            for b in orphans:
                # warm promotion: a live replica already serves this bucket
                # off shared FS — make it the new primary and retire the
                # grant from the replica set (a worker is never its own
                # replica); otherwise least-loaded live worker
                target = None
                for w in self._replicas.get(b, ()):
                    s = self._slots.get(w)
                    if s is not None and s.alive:
                        target = s
                        break
                if target is not None:
                    rest = [w for w in self._replicas[b] if w != target.wid]
                    if rest:
                        self._replicas[b] = rest
                    else:
                        del self._replicas[b]
                else:
                    target = min(live, key=lambda s: len(s.buckets))
                self._grant(target, [b])
        if orphans or pruned:
            self._route_epoch += 1
            g.gauge("replicas_active").set(sum(len(v) for v in self._replicas.values()))
        if orphans:
            g.counter("reassignments").inc(len(orphans))
        g.gauge("workers_live").set(sum(1 for s in self._slots.values() if s.alive))

    def _reap_loop(self) -> None:
        while not self.stop_event.wait(min(self.cfg.heartbeat_timeout_s / 4, 0.5)):
            now = time.monotonic()
            with self._lock:
                for slot in self._slots.values():
                    if slot.alive and now - slot.last_heartbeat > self.cfg.heartbeat_timeout_s:
                        self._reassign_dead(slot)
                if self._rescale is not None and now > self._rescale["deadline"]:
                    self._abort_rescale_locked()
            if self.replica_threshold > 0 and now >= self._next_replica_pass:
                self._next_replica_pass = now + self.replica_interval_s
                try:
                    self._replica_pass()
                except Exception:  # noqa: BLE001 — placement is best-effort
                    pass

    def _abort_rescale_locked(self) -> None:
        """Rescale timed out (a straggler never shipped): drop the state and
        re-grant every live worker its current buckets — the fresh epochs
        resync the fleet and ingest resumes; the rewrite files already
        shipped are unreferenced and fall to the orphan sweep."""
        self._rescale = None
        for slot in self._slots.values():
            if slot.alive and slot.buckets:
                slot.tasks = [t for t in slot.tasks if t.get("kind") != "rescale"]
                self._grant(slot, sorted(slot.buckets))
        self._metrics().counter("rescale_aborts").inc()

    # ---- replica placement (hot-shard serving, ISSUE 19) ----------------
    def _replica_pass(self) -> None:
        """Grant read replicas for hot buckets; demote cooled ones.

        Heat per bucket = serve-side get EMA (reported by workers in
        heartbeats) + write-heat EMA from the adaptive compactor's
        observation loop. Crossing `cluster.replica.heat-threshold` grants a
        secondary owner (least-replica-loaded live worker that is not the
        primary) for get_batch/subscribe/scan_frag off shared FS; dropping
        under HALF the threshold demotes (hysteresis, no flapping). The
        primary keeps writes. Every change bumps the route epoch."""
        g = self._metrics()
        now = time.monotonic()
        with self._lock:
            dt = (now - self._heat_t) if self._heat_t is not None else None
            self._heat_t = now
            if dt and dt > 0:
                # drain only when there is an interval to rate the counts
                # over — the first pass must NOT discard gets that landed
                # before it (a warm client can burst its whole workload in
                # under one pass interval)
                counts, self._get_counts = self._get_counts, {}
                seen = set(counts) | set(self._heat_ema)
                for b in seen:
                    inst = counts.get(b, 0) / dt
                    prev = self._heat_ema.get(b, inst)
                    self._heat_ema[b] = 0.5 * prev + 0.5 * inst
            wheat = self.compaction.heat() if self.compaction is not None else {}
            live = [
                s
                for s in self._slots.values()
                if s.alive and s.serve_addr is not None and s.wid not in self._retiring
            ]
            if self._rescale is not None:
                return  # placement waits out the rescale window
            rload = {s.wid: 0 for s in live}
            for wids in self._replicas.values():
                for w in wids:
                    if w in rload:
                        rload[w] += 1
            changed = False
            for b in range(self.num_buckets):
                heat = self._heat_ema.get(b, 0.0) + float(wheat.get(b, 0.0))
                cur = [w for w in self._replicas.get(b, []) if any(s.wid == w for s in live)]
                if cur != self._replicas.get(b, []):
                    changed = True
                primary = self._owner.get(b)
                if heat >= self.replica_threshold and len(cur) < self.replica_max:
                    cands = [s for s in live if s.wid != primary and s.wid not in cur]
                    if cands:
                        pick = min(cands, key=lambda s: (rload.get(s.wid, 0), len(s.buckets), s.wid))
                        cur = cur + [pick.wid]
                        rload[pick.wid] = rload.get(pick.wid, 0) + 1
                        changed = True
                elif cur and heat < self.replica_threshold * 0.5:
                    cur = []
                    changed = True
                if cur:
                    self._replicas[b] = cur
                elif b in self._replicas:
                    del self._replicas[b]
            if changed:
                self._route_epoch += 1
            g.gauge("replicas_active").set(sum(len(v) for v in self._replicas.values()))

    # ---- compaction dispatch (the execute_group seam) ------------------
    def _dispatch_group(self, group: list, deep: bool) -> int:
        """AdaptiveCompactorService execution seam: queue each decision on
        the worker owning its bucket (skipping buckets already in flight);
        the commit happens later, when the worker ships the result."""
        g = self._metrics()
        dispatched = 0
        with self._lock:
            if self._rescale is not None or self._rescale_committing:
                return 0  # bucket ids are about to change meaning
            for d in group:
                key = (d.partition, d.bucket)
                if key in self._compact_inflight:
                    continue
                wid = self._owner.get(d.bucket)
                slot = self._slots.get(wid) if wid is not None else None
                if slot is None or not slot.alive:
                    continue
                self._task_seq += 1
                task_id = self._task_seq
                self._compact_inflight[key] = (task_id, wid)
                self._task_groups[task_id] = [d]
                slot.tasks.append(
                    {
                        "task_id": task_id,
                        "partition": list(d.partition),
                        "bucket": d.bucket,
                        "deep": bool(deep or d.deep),
                        "trigger": self.compaction.policy.trigger,
                    }
                )
                dispatched += 1
        if dispatched:
            g.counter("compact_tasks").inc(dispatched)
        return dispatched

    # ---- RPC handlers --------------------------------------------------
    def handle(self, method: str, req: dict) -> dict:
        fn = getattr(self, f"_m_{method}", None)
        if fn is None:
            raise ValueError(f"unknown method {method!r}")
        out = fn(req)
        # push-based route invalidation: every reply carries the route
        # epoch and bucket count, so any client touching the coordinator
        # for ANY reason learns about reassignments/rescales/replica
        # changes immediately — including workers whose rescale shipment
        # reply races the final commit
        out.setdefault("route_epoch", self._route_epoch)
        out.setdefault("num_buckets", self.num_buckets)
        return out

    def _flags(self) -> dict:
        return {"go": self.go_event.is_set(), "stop": self.stop_event.is_set()}

    def _m_ping(self, req: dict) -> dict:
        return {}

    def _m_register(self, req: dict) -> dict:
        wid = int(req["worker"])
        g = self._metrics()
        with self._lock:
            slot = self._slots.setdefault(wid, _WorkerSlot(wid))
            slot.incarnation = int(req.get("incarnation", 0))
            slot.alive = True
            slot.last_heartbeat = time.monotonic()
            if req.get("serve_port"):
                slot.serve_addr = (req.get("serve_host", "127.0.0.1"), int(req["serve_port"]))
            if wid in self._retiring:
                # a retiring worker (or its respawn after a mid-handoff
                # kill) gets nothing — the heartbeat retire flag drains it
                pass
            elif not slot.buckets:
                # first registration gets the home range; a respawn whose
                # range was already reassigned steals it back (bounded
                # churn, keeps every live worker productive) — the epoch
                # bump fences the previous owner's in-flight rounds
                want = [b for b in self._home.get(wid, []) if self._owner.get(b) != wid]
                want += [b for b in self._pending if b not in want]
                if not want and self._rescale is None:
                    # runtime scale-out: a joining worker outside the home
                    # split plans a range handoff — steal buckets from the
                    # most-loaded live peers toward an even share; each
                    # grant's epoch bump fences the donor's in-flight round
                    # (the one fencing round), nothing else is rejected
                    want = self._plan_join_steal(wid)
                    if want:
                        g.counter("handoffs").inc()
                self._grant(slot, want)
            else:
                # same buckets, fresh epoch: the PREVIOUS incarnation's
                # late messages must not be accepted as this one's
                self._grant(slot, sorted(slot.buckets))
            g.counter("workers_registered").inc()
            g.gauge("workers_live").set(sum(1 for s in self._slots.values() if s.alive))
            g.gauge("buckets_assigned").set(len(self._owner))
            return {
                "epoch": slot.epoch,
                "buckets": sorted(slot.buckets),
                "num_buckets": self.num_buckets,
                **self._flags(),
            }

    def _plan_join_steal(self, wid: int) -> list[int]:
        """Pick buckets for a joining worker: repeatedly take the highest
        bucket from the currently most-loaded live donor (never stripping a
        donor bare) until the joiner holds an even share. Caller holds the
        lock; the buckets move via the caller's _grant."""
        donors = [s for s in self._slots.values() if s.alive and s.wid != wid and s.buckets]
        total = sum(len(s.buckets) for s in donors)
        target = total // (len(donors) + 1) if donors else 0
        sizes = {s.wid: len(s.buckets) for s in donors}
        steal: list[int] = []
        taken: set[int] = set()
        while len(steal) < target:
            donor = max(donors, key=lambda s: (sizes[s.wid], s.wid))
            if sizes[donor.wid] <= 1:
                break
            pool = [b for b in donor.buckets if b not in taken]
            if not pool:
                break
            b = max(pool)
            steal.append(b)
            taken.add(b)
            sizes[donor.wid] -= 1
        return steal

    def _m_heartbeat(self, req: dict) -> dict:
        wid = int(req["worker"])
        gets = req.get("gets") or {}
        with self._lock:
            for b, n in gets.items():
                self._get_counts[int(b)] = self._get_counts.get(int(b), 0) + int(n)
            slot = self._slots.get(wid)
            if slot is None:
                return {"reregister": True, **self._flags()}
            slot.last_heartbeat = time.monotonic()
            if not slot.alive:
                # declared dead but actually alive (slow round): it must
                # re-register to get a fresh (possibly different) range
                return {"reregister": True, **self._flags()}
            out = {
                "epoch": slot.epoch,
                "buckets": sorted(slot.buckets),
                "num_buckets": self.num_buckets,
                **self._flags(),
            }
            if wid in self._retiring:
                out["retire"] = True
            return out

    def _m_admit(self, req: dict) -> dict:
        """Cluster-wide debt-admission gate: non-blocking here, the worker
        retries with backoff (an RPC thread parked in wait_for would pin
        the server thread pool)."""
        wid = int(req["worker"])
        ident = int(req["ident"])
        buckets = [int(b) for b in req.get("buckets", ())]
        with self._lock:
            if self._rescale is not None or self._rescale_committing:
                # the rescale window: no new rounds start, the already
                # admitted in-flight ones get fenced at ship — the worker
                # sees `rescaling` and goes execute its rewrite task
                self._metrics().counter("admit_denied").inc()
                return {"admitted": False, "retry_after_ms": 200, "rescaling": True}
        if self.compaction is None:
            return {"admitted": True}
        key = (wid, ident)
        with self._lock:
            if key in self._admit_charges:
                return {"admitted": True}  # idempotent retry of the RPC
        ok = self.compaction.admit(
            buckets=[((), b) for b in buckets], timeout_s=0.0, project=True, owner=wid
        )
        if ok:
            with self._lock:
                self._admit_charges[key] = buckets
            return {"admitted": True}
        self._metrics().counter("admit_denied").inc()
        return {"admitted": False, "retry_after_ms": 100}

    def _settle_charges(self, wid: int, ident: int, landed: bool) -> None:
        with self._lock:
            buckets = self._admit_charges.pop((wid, ident), None)
        if buckets and self.compaction is not None:
            self.compaction.settle([((), b) for b in buckets], landed=landed, owner=wid)

    def _check_fence(self, slot: _WorkerSlot, epoch: int, buckets: list[int]) -> bool:
        """True when every bucket is still owned by the shipper at an epoch
        it has seen — the reassignment fence."""
        for b in buckets:
            if self._owner.get(b) != slot.wid or self._bucket_epoch.get(b, 1 << 62) > epoch:
                return False
        return True

    def _commit_store(self, wid: int):
        from ..table import load_table

        store = self._commit_stores.get(wid)
        if store is None:
            store = load_table(self.table_path, commit_user=f"{self.USER_PREFIX}{wid}").store
            self._commit_stores[wid] = store
        return store

    def _m_ship_commit(self, req: dict) -> dict:
        from ..core.commit import CommitConflictError, CommitGiveUpError
        from ..core.manifest import CommitMessage, ManifestCommittable

        wid = int(req["worker"])
        epoch = int(req["epoch"])
        kind = req.get("kind", "append")
        msgs = [CommitMessage.from_dict(m) for m in req.get("messages", ())]
        # a rescale shipment's messages carry NEW bucket ids, which nobody
        # owns under the old routing — the fence checks the OLD buckets the
        # task covered instead
        if kind == "rescale":
            touched = sorted(int(b) for b in req.get("buckets", ()))
        else:
            touched = sorted({m.bucket for m in msgs})
        g = self._metrics()
        with self._lock:
            slot = self._slots.get(wid)
            stale = slot is None or not self._check_fence(slot, epoch, touched)
        if kind == "rescale":
            return self._commit_rescale_part(req, msgs, touched, stale)
        if kind == "compact":
            return self._commit_compact(req, msgs, stale)
        ident = int(req["ident"])
        if stale:
            # the whole round is one commit: one reassigned bucket rejects
            # the shipment (never a partial apply of a fenced-off round)
            g.counter("commits_rejected_stale").inc()
            self._settle_charges(wid, ident, landed=False)
            return {"stale": True, "sid": None}
        with self._lock:
            prior = slot.committed.get(ident)
        if prior is not None:
            return {"sid": prior, "stale": False}  # idempotent re-ship
        store = self._commit_store(wid)
        sid = None
        try:
            sids = store.new_commit().commit(ManifestCommittable(ident, messages=msgs))
            sid = sids[0] if sids else None
        except (CommitConflictError, CommitGiveUpError):
            # the APPEND half may have landed before the loss — the chain,
            # not the exception, is the truth (PR 8 protocol)
            sid = find_landed_append(store, f"{self.USER_PREFIX}{wid}", ident)
        if sid is not None:
            with self._lock:
                slot.committed[ident] = sid
            g.counter("rounds_committed").inc()
        self._settle_charges(wid, ident, landed=sid is not None)
        return {"sid": sid, "stale": False}

    def _commit_compact(self, req: dict, msgs: list, stale: bool) -> dict:
        from ..core.commit import BATCH_COMMIT_IDENTIFIER, CommitConflictError, CommitGiveUpError
        from ..core.manifest import ManifestCommittable

        g = self._metrics()
        task_id = int(req.get("task_id", 0))
        with self._lock:
            group = self._task_groups.pop(task_id, None)
            for key, (tid, _w) in list(self._compact_inflight.items()):
                if tid == task_id:
                    del self._compact_inflight[key]
        if stale:
            g.counter("commits_rejected_stale").inc()
            return {"stale": True, "sid": None}
        if not msgs:
            return {"sid": None, "stale": False}
        try:
            sids = self.table.store.new_commit().commit(
                ManifestCommittable(BATCH_COMMIT_IDENTIFIER, messages=msgs)
            )
        except (CommitConflictError, CommitGiveUpError):
            # lost to a rival commit: abandoned, fresh state next round
            g.counter("compact_conflicts").inc()
            return {"sid": None, "stale": False}
        if group and self.compaction is not None:
            self.compaction.note_compaction_landed(group)
        g.counter("compact_commits").inc()
        return {"sid": sids[0] if sids else None, "stale": False}

    # ---- cross-worker dynamic-bucket rescale (ISSUE 19 tentpole) --------
    def start_rescale(self, new_buckets: int) -> dict:
        """Begin a coordinator-driven rescale to `new_buckets`.

        One global epoch bump fences EVERY bucket at once — the one fencing
        round: in-flight appends/compacts admitted before this instant get
        rejected stale at ship, new admits are denied for the window, and
        compaction dispatch pauses. Each live owner is handed a rescale
        task (its owned old buckets + the pinned snapshot); the rewrites
        ship back as kind="rescale" CommitMessages and land atomically in
        `_finish_rescale` once every old bucket is covered. Readers pinned
        at or before the snapshot stay bit-identical throughout."""
        new_buckets = int(new_buckets)
        if new_buckets < 1:
            return {"started": False, "reason": "bad-bucket-count"}
        snap = self.table.store.snapshot_manager.latest_snapshot()
        with self._lock:
            if self._rescale is not None or self._rescale_committing:
                return {"started": False, "reason": "rescale-in-progress"}
            if new_buckets == self.num_buckets:
                return {"started": False, "reason": "already-at-count"}
            if snap is None:
                return {"started": False, "reason": "empty-table"}
            self._epoch += 1
            for b in range(self.num_buckets):
                self._bucket_epoch[b] = self._epoch
            self._rescale = {
                "new": new_buckets,
                "snapshot": snap.id,
                "epoch": self._epoch,
                "needed": set(range(self.num_buckets)),
                "done": set(),
                "msgs": [],
                "deadline": time.monotonic() + self.rescale_timeout_s,
            }
            self._route_epoch += 1
            for slot in self._slots.values():
                if slot.alive and slot.buckets:
                    slot.tasks.append(self._rescale_task(sorted(slot.buckets)))
        return {"started": True, "snapshot": snap.id, "new_buckets": new_buckets}

    def _m_rescale(self, req: dict) -> dict:
        return self.start_rescale(int(req["new_buckets"]))

    def _m_rescale_status(self, req: dict) -> dict:
        with self._lock:
            rs = self._rescale
            return {
                "active": rs is not None or self._rescale_committing,
                "num_buckets": self.num_buckets,
                "done": sorted(rs["done"]) if rs else [],
            }

    def _commit_rescale_part(self, req: dict, msgs: list, covered: list[int], stale: bool) -> dict:
        g = self._metrics()
        with self._lock:
            rs = self._rescale
            if rs is None or stale:
                g.counter("commits_rejected_stale").inc()
                return {"stale": True, "sid": None}
            fresh = [b for b in covered if b in rs["needed"] and b not in rs["done"]]
            if not fresh:
                return {"stale": False, "sid": None, "dup": True}
            rs["done"].update(fresh)
            rs["msgs"].extend(msgs)
            complete = rs["done"] >= rs["needed"]
            if complete:
                # flip to the committing phase under the lock: admits stay
                # denied and no rival _finish_rescale can start
                self._rescale = None
                self._rescale_committing = True
        if not complete:
            return {"stale": False, "sid": None}
        return self._finish_rescale(rs)

    def _finish_rescale(self, rs: dict) -> dict:
        """Every old bucket rewritten: commit schema-(N+1) (`bucket` option
        bump) + ONE OVERWRITE snapshot, then atomically republish routing at
        the new bucket count (fresh contiguous split over live workers).
        Old data files stay on disk until snapshot expiry, so readers
        pinned pre-rescale keep their bit-identical view."""
        from ..table import load_table
        from ..table.rescale import commit_rescale

        g = self._metrics()
        try:
            sid = commit_rescale(self.table, rs["new"], rs["msgs"])
        except Exception:
            with self._lock:
                self._rescale_committing = False
                self._abort_rescale_locked()
            raise
        with self._lock:
            self.table = load_table(self.table_path, commit_user="cluster-coordinator")
            self.num_buckets = rs["new"]
            self._owner.clear()
            self._bucket_epoch.clear()
            self._pending.clear()
            self._commit_stores.clear()  # per-wid stores hold old-layout tables
            self._replicas.clear()  # bucket ids changed meaning
            self._heat_ema.clear()
            self._get_counts.clear()
            self._home = self._split_ranges()
            live = sorted((s for s in self._slots.values() if s.alive), key=lambda s: s.wid)
            for s in self._slots.values():
                s.buckets.clear()
                s.tasks = [t for t in s.tasks if t.get("kind") != "rescale"]
            if live:
                n, w = self.num_buckets, len(live)
                for i, s in enumerate(live):
                    self._grant(s, list(range(i * n // w, (i + 1) * n // w)))
            else:
                self._pending.extend(range(self.num_buckets))
            self._rescale_committing = False
            self._route_epoch += 1
            g.gauge("replicas_active").set(0)
            g.gauge("buckets_assigned").set(len(self._owner))
        if self.compaction is not None:
            self.compaction.table = self.table
        g.counter("rescales").inc()
        return {"stale": False, "sid": sid, "rescaled": rs["new"]}

    # ---- planned worker retire (scale-in) -------------------------------
    def request_retire(self, wid: int) -> None:
        """Flag `wid` for planned drain: the next heartbeat reply carries
        `retire`, the worker finishes its in-flight round, settles its
        charges, and calls the retire RPC for the range handoff."""
        with self._lock:
            self._retiring.add(int(wid))

    def _m_request_retire(self, req: dict) -> dict:
        self.request_retire(int(req["worker"]))
        return {}

    def _m_retire(self, req: dict) -> dict:
        """The drained worker's handoff: a planned retire is a death without
        the timeout — the same reassignment machinery moves its range (one
        fencing round), releases its debt-gate charges, and prunes its
        replica grants; the worker then exits clean."""
        wid = int(req["worker"])
        g = self._metrics()
        with self._lock:
            self._retiring.discard(wid)
            slot = self._slots.get(wid)
            if slot is None or not slot.alive:
                return {"retired": True}
            had = bool(slot.buckets)
            self._reassign_dead(slot)
            if had:
                g.counter("handoffs").inc()
        return {"retired": True}

    def _m_poll_work(self, req: dict) -> dict:
        wid = int(req["worker"])
        epoch = int(req["epoch"])
        with self._lock:
            slot = self._slots.get(wid)
            if slot is None:
                return {"tasks": [], "resync": True, **self._flags()}
            if slot.epoch != epoch:
                # stale poller (its range moved, or a rescale republished
                # routing): hand back the current assignment so it resyncs
                # on this reply instead of waiting out a heartbeat
                if slot.alive:
                    return {
                        "tasks": [], "resync": True, "epoch": slot.epoch,
                        "buckets": sorted(slot.buckets), **self._flags(),
                    }
                return {"tasks": [], "resync": True, **self._flags()}
            tasks, slot.tasks = slot.tasks, []
            return {"tasks": tasks, **self._flags()}

    def _m_barrier(self, req: dict) -> dict:
        """Named phase barrier (bench mode: every worker finishes ingest
        before anyone's timed merge-read pins the final state)."""
        name = str(req["name"])
        wid = int(req["worker"])
        expected = int(req.get("expected", self.cfg.workers))
        with self._lock:
            members = self._barriers.setdefault(name, set())
            members.add(wid)
            return {"released": len(members) >= expected}

    def _m_worker_done(self, req: dict) -> dict:
        wid = int(req["worker"])
        with self._lock:
            slot = self._slots.get(wid)
            if slot is not None:
                slot.done_stats = dict(req.get("stats", {}))
        return {}

    def _m_route(self, req: dict) -> dict:
        with self._lock:
            workers = {
                str(wid): {
                    "host": slot.serve_addr[0] if slot.serve_addr else None,
                    "port": slot.serve_addr[1] if slot.serve_addr else None,
                    "buckets": sorted(slot.buckets),
                    "epoch": slot.epoch,
                }
                for wid, slot in self._slots.items()
                if slot.alive
            }
            replicas = {str(b): list(wids) for b, wids in self._replicas.items()}
        return {"workers": workers, "num_buckets": self.num_buckets, "replicas": replicas}

    def _m_status(self, req: dict) -> dict:
        with self._lock:
            return {
                "workers": {
                    str(w): {
                        "alive": s.alive,
                        "buckets": sorted(s.buckets),
                        "epoch": s.epoch,
                        "commits": len(s.committed),
                        "done": s.done_stats,
                    }
                    for w, s in self._slots.items()
                },
                "pending_buckets": list(self._pending),
                "compact_inflight": len(self._compact_inflight),
            }

    # supervisor-side helpers (same process)
    def assignment_of(self, wid: int) -> tuple[int, list[int]]:
        with self._lock:
            slot = self._slots.get(wid)
            return (slot.epoch, sorted(slot.buckets)) if slot else (0, [])

    def all_done(self) -> bool:
        with self._lock:
            return bool(self._slots) and all(
                s.done_stats is not None for s in self._slots.values()
            )


# ---------------------------------------------------------------------------
# RPC client plumbing (shared by workers and ClusterClient)
# ---------------------------------------------------------------------------
class _RpcConn:
    """One persistent length-prefixed-JSON connection, thread-safe."""

    def __init__(self, host: str, port: int, timeout: float = 60.0):
        self._sock = socket.create_connection((host, port), timeout=timeout)
        self._lock = threading.Lock()
        self._id = 0

    def call(self, method: str, **kw) -> dict:
        with self._lock:
            self._id += 1
            _send(self._sock, {"id": self._id, "method": method, **kw})
            resp = _recv(self._sock)
        if resp is None:
            raise ConnectionError(f"{method}: server closed the connection")
        if not resp.get("ok"):
            raise RuntimeError(resp.get("error", f"{method} failed"))
        return resp

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass

    def cancel(self) -> None:
        """Abort an in-flight call from ANOTHER thread. shutdown() unblocks
        a peer stuck in recv (close() alone need not), so the blocked call
        raises ConnectionError — the gateway's hedge-loser teardown.

        Deliberately NOT close(): the blocked caller still owns this fd.
        Closing here frees the fd number for reuse while that caller may be
        an instruction away from recv()ing on it — it would then block
        forever stealing a brand-new connection's replies. The caller's
        error path discards (closes) the connection itself."""
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass


def _row_buckets(table, batch) -> np.ndarray:
    """(n,) int32 bucket id per row of a value batch (fixed-bucket route)."""
    from ..table.bucket import bucket_ids

    return bucket_ids(batch, table.schema.bucket_keys, max(table.store.options.bucket, 1))


def bucket_key_pools(num_buckets: int, base: int, count_per_bucket: int) -> dict[int, np.ndarray]:
    """Deterministic per-bucket key pools: scan candidate keys base+[0, M)
    in vector chunks, bucketize with the table's own hash, and keep the
    first `count_per_bucket` keys landing in each bucket. Identical in
    every process for identical args — the bench's worker-count-independent
    row generator and the soak's owned-bucket key source."""
    from ..data.batch import ColumnBatch
    from ..table.bucket import bucket_ids
    from ..types import BIGINT, RowType

    rt = RowType.of(("k", BIGINT()))
    pools: dict[int, list] = {b: [] for b in range(num_buckets)}
    start = base
    while any(len(p) < count_per_bucket for p in pools.values()):
        ks = np.arange(start, start + 4096, dtype=np.int64)
        start += 4096
        bs = bucket_ids(ColumnBatch.from_pydict(rt, {"k": ks}), ["k"], num_buckets)
        for b in range(num_buckets):
            need = count_per_bucket - len(pools[b])
            if need > 0:
                pools[b].extend(ks[bs == b][:need].tolist())
    return {b: np.asarray(p, dtype=np.int64) for b, p in pools.items()}


# ---------------------------------------------------------------------------
# worker serving plane: get_batch + subscribe + join_part on the worker
# ---------------------------------------------------------------------------
class _WorkerServer:
    """The worker's request plane (closes the PR 13/14 follow-ups: gets and
    subscriptions served FROM the mesh workers). LocalTableQuery rides the
    subscription-driven refresher (query.follow — one decode-once tailer
    keeps every touched bucket's probe index fresh); subscriptions filter
    each fanned batch to the requested buckets so a routed client folds
    exactly its shard's changelog."""

    def __init__(
        self,
        table,
        owned: "callable",
        host: str = "127.0.0.1",
        port: int = 0,
        delay_ms: "float | None" = None,
        route_epoch: "callable | None" = None,
    ):
        from ..options import CoreOptions
        from ..table.query import LocalTableQuery
        from .subscription import SubscriptionHub

        self.table = table
        self._owned = owned  # () -> set[int], the worker's live bucket set
        self._route_epoch = route_epoch  # () -> int, piggybacked on replies
        self._get_counts: dict[int, int] = {}  # bucket -> gets (heat report)
        self._lock = threading.Lock()
        # injected straggler latency on the read plane (get_batch/scan_frag):
        # the gateway bench/storm latency-shame one worker to measure hedging
        if delay_ms is None:
            delay_ms = float(os.environ.get("PAIMON_TPU_WORKER_SERVE_DELAY_MS", "0"))
        self._delay_ms = float(delay_ms)
        self._closed = False
        # scan_frag admission (ISSUE 16, the PR 13 semaphore + retry_after
        # pattern): a scan storm sheds typed-BUSY instead of starving the
        # get/subscribe serving this plane exists for
        self._scan_slots = threading.BoundedSemaphore(
            max(1, int(table.store.options.options.get(CoreOptions.SQL_CLUSTER_SCAN_MAX_INFLIGHT)))
        )
        # shuffle exchange plane (ISSUE 20). Admission is a SEPARATE
        # semaphore from _scan_slots: a scan_frag HOLDS its scan slot while
        # delivering parts to peer owners, so shared admission would
        # livelock a fleet of mutually-delivering workers into circular
        # BUSY retries. Buffers are TTL-GC'd; a coordinator that finishes
        # cleanly closes them explicitly (exchange_close).
        self._exch_slots = threading.BoundedSemaphore(
            max(2, 2 * int(table.store.options.options.get(CoreOptions.SQL_CLUSTER_SCAN_MAX_INFLIGHT)))
        )
        self._exch_lock = threading.Lock()
        # inbound: qid -> {"ts", "parts": {(range, src): wire partial}} —
        # delivery is keyed, so hedged/re-executed duplicates overwrite
        # with bit-identical content instead of double-counting
        self._exch_in: dict[str, dict] = {}
        # outbound (the reship buffer): (qid, src) -> {"ts", "parts":
        # {range: wire partial}} — survives the range owner, not the source
        self._exch_out: dict[tuple, dict] = {}
        self._peer_conns: dict[tuple, _RpcConn] = {}
        # one hub per worker process: the refresher AND every routed
        # subscription share its decode-once tailer; the server owns its
        # lifecycle (for_table hubs outlive their subscribers by design)
        self._hub = SubscriptionHub.for_table(table)
        self.query = LocalTableQuery(table)
        self.query.follow(hub=self._hub, lock=self._lock)
        self._subs: dict[str, object] = {}
        self._sub_seq = 0
        outer = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                while True:
                    req = _recv(self.request)
                    if req is None:
                        return
                    rid = req.pop("id", None)
                    method = req.pop("method", "")
                    try:
                        out = outer._dispatch(method, req)
                        out["id"] = rid
                        out.setdefault("ok", True)
                        if outer._route_epoch is not None:
                            # push invalidation rides the serving plane too:
                            # a client talking only to workers still learns
                            # of reassignments the moment they happen
                            out.setdefault("route_epoch", int(outer._route_epoch()))
                    except Exception as e:  # noqa: BLE001
                        out = {"id": rid, "ok": False, "error": f"{type(e).__name__}: {e}"}
                    _send(self.request, out)

        self._server = socketserver.ThreadingTCPServer((host, port), Handler, bind_and_activate=True)
        self._server.daemon_threads = True
        self.host, self.port = self._server.server_address
        threading.Thread(target=self._server.serve_forever, daemon=True).start()

    def _metrics(self):
        from ..metrics import cluster_metrics

        return cluster_metrics()

    def _dispatch(self, method: str, req: dict) -> dict:
        if method == "ping":
            return {"buckets": sorted(self._owned())}
        if self._closed and method in (
            "get_batch",
            "subscribe_open",
            "scan_frag",
            "exchange_part",
            "exchange_combine",
            "exchange_reship",
        ):
            # shutdown race (ISSUE 17 bugfix hunt): a request landing while
            # close() tears down the hub must answer a TYPED shed, not leak
            # a fresh hub/tailer out of a re-created subscription
            from .shed import ShedInfo

            return ShedInfo(kind="request", state="shutting-down", retry_after_ms=100).to_payload()
        if method in ("get_batch", "scan_frag") and self._delay_ms > 0:
            time.sleep(self._delay_ms / 1000.0)
        if method == "get_batch":
            ks = [tuple(k) if isinstance(k, list) else (k,) for k in req["keys"]]
            with self._lock:
                res = self.query.get_batch(ks, tuple(req.get("partition", ())))
            self._metrics().counter("serve_gets").inc(len(ks))
            self._note_gets(ks)
            return {"rows": [None if r is None else list(r) for r in res.to_pylist()]}
        if method == "subscribe_open":
            # _sub_seq increments under the lock: two concurrent opens in
            # separate handler threads must never mint the same sub_id (the
            # shadowed Subscription would leak its consumer slot)
            with self._lock:
                self._sub_seq += 1
                sub_id = f"s{self._sub_seq}"
            self._subs[sub_id] = (
                self._hub.subscribe(
                    consumer_id=req.get("consumer_id"),
                    from_snapshot=req.get("from_snapshot"),
                ),
                [int(b) for b in req.get("buckets", [])] or None,
            )
            return {"sub_id": sub_id}
        if method == "subscribe_poll":
            return self._subscribe_poll(req)
        if method == "subscribe_close":
            sub, _ = self._subs.pop(req["sub_id"], (None, None))
            if sub is not None:
                sub.close(delete_consumer=bool(req.get("delete_consumer")))
            return {}
        if method == "join_part":
            return self._join_part(req)
        if method == "scan_frag":
            return self._scan_frag(req)
        if method == "exchange_part":
            return self._exchange_part(req)
        if method == "exchange_combine":
            return self._exchange_combine(req)
        if method == "exchange_reship":
            return self._exchange_reship(req)
        if method == "exchange_close":
            return self._exchange_close(req)
        raise ValueError(f"unknown method {method!r}")

    def _scan_frag(self, req: dict) -> dict:
        """One distributed-SQL scan fragment (ISSUE 16): rebuild the shipped
        splits, scan + reduce locally (table.query.execute_scan_fragment),
        ship the partial back. Admission is typed-BUSY under
        sql.cluster.scan.max-inflight; sheds count into soak{shed_requests}
        beside every other serving-plane BUSY."""
        if not self._scan_slots.acquire(blocking=False):
            from ..metrics import soak_metrics
            from .shed import ShedInfo

            soak_metrics().counter("shed_requests").inc()
            return ShedInfo(kind="sql", state="busy-scan", retry_after_ms=50).to_payload()
        try:
            from ..sql.cluster import decode_fragment, encode_partial
            from ..table.query import execute_scan_fragment

            frag = decode_fragment(req["frag"])
            part = execute_scan_fragment(self.table, frag)
            self._metrics().counter("scan_frags_served").inc()
            if frag.get("shuffle") and part["mode"] == "agg":
                return {"partial": self._shuffle_out(frag, part)}
            return {"partial": encode_partial(part, code_domain=bool(frag.get("code_domain", True)))}
        finally:
            self._scan_slots.release()

    # ---- shuffle exchange plane (ISSUE 20) ------------------------------
    _EXCHANGE_TTL_S = 600.0

    def _shuffle_out(self, frag: dict, part: dict) -> dict:
        """Shuffle-source tail of scan_frag: hash-partition the fragment
        partial by group-key VALUE into the plan's R ranges
        (table.query.partition_agg_partial), buffer every nonempty part for
        reship, deliver each to its range owner, and answer a summary whose
        `sent` map is the coordinator's per-range expectation source. A
        delivery that fails is swallowed — the part stays buffered and the
        coordinator reships/recovers at combine time; failing the scan here
        would throw away a perfectly good partial."""
        from ..sql.cluster import encode_partial, wire_partial_bytes
        from ..table.query import partition_agg_partial

        qid, src = frag["shuffle"]["qid"], frag["src"]
        ranges = frag["shuffle"]["ranges"]
        code_domain = bool(frag.get("code_domain", True))
        parts = partition_agg_partial(part, len(ranges))
        wire = {
            r: encode_partial(pt, code_domain=code_domain)
            for r, pt in enumerate(parts)
            if pt is not None
        }
        now = time.monotonic()
        with self._exch_lock:
            self._gc_exchange_locked()
            self._exch_out[(qid, src)] = {"ts": now, "parts": wire}
        sent: dict = {}
        nbytes = 0

        def _ship(r, wp):
            try:
                self._deliver_part(ranges[r][1], int(ranges[r][2]), qid, r, src, wp)
            except (ConnectionError, OSError, TimeoutError):
                pass  # dead/slow owner: coordinator heals it at combine time

        # concurrent deliveries: each remote part pays a full serialize +
        # round-trip, and a source owes R-1 of them — overlapping them keeps
        # the scatter's critical path at ~one part instead of R-1
        remote = []
        for r, wp in wire.items():
            nbytes += wire_partial_bytes(wp)
            sent[str(r)] = int(parts[r]["rows"])
            if (ranges[r][1], int(ranges[r][2])) == (self.host, self.port):
                _ship(r, wp)  # self-delivery is a buffer insert, no wire
            else:
                remote.append((r, wp))
        if len(remote) == 1:
            _ship(*remote[0])
        elif remote:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(max_workers=len(remote)) as pool:
                for f in [pool.submit(_ship, r, wp) for r, wp in remote]:
                    f.result()
        self._metrics().counter("exchange_parts_sent").inc(len(wire))
        return {
            "mode": "shuffle",
            "src": src,
            "rows": int(part["rows"]),
            "rows_reduced_device": int(part.get("rows_reduced_device", 0)),
            "sent": sent,
            "bytes": int(nbytes),
        }

    def _deliver_part(
        self, host: str, port: int, qid: str, rng: int, src: str, wp: dict, busy_wait_s: float = 10.0
    ) -> None:
        """Ship one buffered part to a range owner. Self-delivery drops
        straight into the inbound buffer (no wire); remote delivery absorbs
        typed-BUSY with the advertised backoff and raises on a dead peer."""
        if (host, int(port)) == (self.host, self.port):
            with self._exch_lock:
                box = self._exch_in.setdefault(qid, {"ts": time.monotonic(), "parts": {}})
                box["parts"][(int(rng), src)] = wp
                box["ts"] = time.monotonic()
            return
        deadline = time.monotonic() + busy_wait_s
        while True:
            conn = self._peer_conn(host, int(port))
            try:
                r = conn.call("exchange_part", qid=qid, rng=int(rng), src=src, part=wp)
            except (ConnectionError, OSError):
                self._drop_peer(host, int(port))
                raise
            if not r.get("busy"):
                return
            if time.monotonic() >= deadline:
                raise TimeoutError(f"exchange peer {host}:{port} still BUSY after {busy_wait_s}s")
            time.sleep(float(r.get("retry_after_ms", 50)) / 1000.0)

    def _peer_conn(self, host: str, port: int) -> _RpcConn:
        with self._exch_lock:
            conn = self._peer_conns.get((host, port))
        if conn is not None:
            return conn
        fresh = _RpcConn(host, port, timeout=10.0)  # connect outside the lock
        with self._exch_lock:
            won = self._peer_conns.setdefault((host, port), fresh)
        if won is not fresh:
            fresh.close()
        return won

    def _drop_peer(self, host: str, port: int) -> None:
        with self._exch_lock:
            conn = self._peer_conns.pop((host, port), None)
        if conn is not None:
            conn.close()

    def _gc_exchange_locked(self) -> None:
        cutoff = time.monotonic() - self._EXCHANGE_TTL_S
        for q in [q for q, box in self._exch_in.items() if box["ts"] < cutoff]:
            del self._exch_in[q]
        for k in [k for k, box in self._exch_out.items() if box["ts"] < cutoff]:
            del self._exch_out[k]

    def _exchange_shed(self):
        from ..metrics import soak_metrics
        from .shed import ShedInfo

        soak_metrics().counter("shed_requests").inc()
        return ShedInfo(kind="sql", state="busy-exchange", retry_after_ms=50).to_payload()

    def _exchange_part(self, req: dict) -> dict:
        """Receive one shuffle part from a peer worker (keyed delivery:
        (qid, range, src) — redelivery overwrites idempotently)."""
        if not self._exch_slots.acquire(blocking=False):
            return self._exchange_shed()
        try:
            with self._exch_lock:
                self._gc_exchange_locked()
                box = self._exch_in.setdefault(req["qid"], {"ts": time.monotonic(), "parts": {}})
                box["parts"][(int(req["rng"]), req["src"])] = req["part"]
                box["ts"] = time.monotonic()
            self._metrics().counter("exchange_parts_received").inc()
            return {}
        finally:
            self._exch_slots.release()

    def _exchange_combine(self, req: dict) -> dict:
        """Fold this worker's shuffle range: decode every EXPECTED part
        from the inbound buffer and run the coordinator's own
        combine_partials over them — the range's final reduction, answered
        as one already-reduced partial. Parts still missing (delivery
        failed in flight, or this worker is a fresh replacement owner) are
        named so the coordinator can reship them."""
        if not self._exch_slots.acquire(blocking=False):
            return self._exchange_shed()
        try:
            from ..sql.cluster import combine_partials, decode_partial, encode_partial

            qid, rng = req["qid"], int(req["rng"])
            expect = list(req.get("expect") or [])
            with self._exch_lock:
                parts_map = dict(self._exch_in.get(qid, {}).get("parts", {}))
            have = {src: parts_map.get((rng, src)) for src in expect}
            missing = sorted(src for src, wp in have.items() if wp is None)
            if missing:
                return {"missing": missing}
            group_cols = list(req.get("group_cols") or [])
            kern = [tuple(k) for k in req.get("kern") or []]
            projection = req.get("projection")
            schema = (
                self.table.row_type.project(list(projection))
                if projection is not None
                else self.table.row_type
            )
            parts = [decode_partial(have[src], schema, group_cols) for src in expect]
            parts = [q for q in parts if q["rows"]]
            if not parts:  # unreachable: senders never ship empty parts
                raise ValueError(f"exchange_combine: no nonempty parts for range {rng}")
            pools, codes, outs, anyv, first_pos = combine_partials(
                parts, len(group_cols), kern, req.get("engine", "xla")
            )
            out_part = {
                "mode": "agg",
                "pools": pools,
                "group_codes": codes,
                "outs": outs,
                "anyv": anyv,
                "first_pos": first_pos,
                "rows": int(len(first_pos)),
                "rows_reduced_device": 0,  # the sources already accounted theirs
            }
            self._metrics().counter("exchange_combines_served").inc()
            return {"partial": encode_partial(out_part, code_domain=bool(req.get("code_domain", True)))}
        finally:
            self._exch_slots.release()

    def _exchange_reship(self, req: dict) -> dict:
        """Re-send one buffered outbound part to a (possibly re-homed)
        range owner. Delivery failure answers shipped=false instead of
        raising: the coordinator's next move (re-execute the fragment)
        is the same either way, and an error reply would surface as a
        spurious RuntimeError in its recovery loop."""
        if not self._exch_slots.acquire(blocking=False):
            return self._exchange_shed()
        try:
            qid, src, rng = req["qid"], req["src"], int(req["rng"])
            with self._exch_lock:
                wp = self._exch_out.get((qid, src), {}).get("parts", {}).get(rng)
            if wp is None:
                return {"shipped": False}
            try:
                self._deliver_part(req["host"], int(req["port"]), qid, rng, src, wp)
            except (ConnectionError, OSError, TimeoutError):
                return {"shipped": False}
            self._metrics().counter("exchange_parts_reshipped").inc()
            return {"shipped": True}
        finally:
            self._exch_slots.release()

    def _exchange_close(self, req: dict) -> dict:
        """Drop a finished query's exchange buffers (best-effort; the TTL
        GC catches whatever a dead coordinator leaves behind)."""
        qid = req["qid"]
        with self._exch_lock:
            self._exch_in.pop(qid, None)
            for k in [k for k in self._exch_out if k[0] == qid]:
                del self._exch_out[k]
        return {}

    def _subscribe_poll(self, req: dict) -> dict:
        from ..types import RowKind
        from .subscription import SubscriberShedError

        sub, buckets = self._subs.get(req["sub_id"], (None, None))
        if sub is None:
            raise ValueError(f"unknown subscription {req['sub_id']!r}")
        timeout = float(req.get("timeout_ms", 1000)) / 1000.0
        try:
            batch = sub.poll(timeout=timeout)
        except SubscriberShedError as e:
            self._subs.pop(req["sub_id"], None)
            return {"shed": True, **{k: v for k, v in e.payload.items() if k != "state"}}
        self._metrics().counter("serve_subscribe_polls").inc()
        if batch is None:
            return {"rows": [], "snapshot_id": None, "checkpoint": sub.checkpoint}
        rows = list(zip(batch.data.to_pylist(), batch.kinds.tolist()))
        if buckets is not None:
            mask = _row_buckets(self.table, batch.data)
            rows = [rv for rv, b in zip(rows, mask.tolist()) if b in buckets]
        return {
            "rows": [[RowKind(int(k)).short_string, *r] for r, k in rows],
            "snapshot_id": batch.snapshot_id,
            "checkpoint": sub.checkpoint,
        }

    def _join_part(self, req: dict) -> dict:
        """One JSPIM partition's kernel, executed on this worker (ISSUE 15
        satellite: the skew split spans worker processes)."""
        from ..ops.join import _join_part as run_part

        ll = _unb64(req["ll"])
        rl = _unb64(req["rl"])
        lt, rt = run_part(ll, rl, req.get("algorithm", "sort-merge"), req.get("engine", "numpy"))
        self._metrics().counter("join_parts_served").inc()
        return {"lt": _b64(np.asarray(lt, dtype=np.int64)), "rt": _b64(np.asarray(rt, dtype=np.int64))}

    def _note_gets(self, ks: list) -> None:
        """Fold served probe keys into per-bucket counts — the worker's
        heartbeat ships the deltas, the coordinator's replica planner turns
        them into the serve-read heat EMA."""
        try:
            from ..data.batch import ColumnBatch
            from ..table.bucket import bucket_ids
            from ..types import RowType

            keys = self.table.schema.bucket_keys
            if not ks or len(keys) != 1 or any(len(k) != 1 for k in ks):
                return
            fields = {f.name: f for f in self.table.schema.fields}
            rt = RowType.of((keys[0], fields[keys[0]].type))
            probe = ColumnBatch.from_pydict(rt, {keys[0]: [k[0] for k in ks]})
            bs = bucket_ids(probe, keys, max(self.table.store.options.bucket, 1))
            with self._lock:
                for b in bs.tolist():
                    self._get_counts[b] = self._get_counts.get(b, 0) + 1
        except Exception:  # noqa: BLE001 — heat is advisory, never fail a get
            pass

    def take_get_counts(self) -> dict[int, int]:
        with self._lock:
            out, self._get_counts = self._get_counts, {}
        return out

    def reload_table(self, table) -> None:
        """Swap the serving plane onto a reloaded table (bucket-count change
        after a rescale): a query constructed over the OLD schema would
        bucketize probes hash%old against the new layout — a silent miss.
        The new query refreshes off-lock, then swaps in atomically; the
        shared hub keeps tailing (decode is bucket-count independent)."""
        from ..table.query import LocalTableQuery

        fresh = LocalTableQuery(table)
        with self._lock:
            old_q, self.query = self.query, fresh
            self.table = table
        fresh.follow(hub=self._hub, lock=self._lock)
        try:
            old_q.unfollow()
        except Exception:  # noqa: BLE001
            pass

    def close(self) -> None:
        self._closed = True
        with self._exch_lock:
            peer_conns = list(self._peer_conns.values())
            self._peer_conns.clear()
            self._exch_in.clear()
            self._exch_out.clear()
        for c in peer_conns:
            c.close()
        for sub_id in list(self._subs):
            sub, _ = self._subs.pop(sub_id, (None, None))
            if sub is not None:
                try:
                    sub.close()
                except Exception:
                    pass
        self.query.unfollow()
        try:
            self._hub.close()
        except Exception:
            pass
        self._server.shutdown()
        self._server.server_close()


# ---------------------------------------------------------------------------
# worker agent
# ---------------------------------------------------------------------------
class _KeyGen:
    """Owned-bucket fresh-key source over this worker's private keyspace:
    scan candidates forward from a durable offset, bucketize with the
    table's own hash, keep what lands in owned buckets. The journal records
    (scan_start, scan_span) per intent so a respawned incarnation resumes
    PAST every scanned candidate — a key is never minted twice, landed or
    not, which keeps the fold unambiguous."""

    def __init__(self, num_buckets: int, base: int, offset: int = 0):
        self.num_buckets = num_buckets
        self.base = base
        self.offset = offset

    def take(self, owned: "set[int]", per_bucket: int) -> tuple[dict[int, list[int]], int, int]:
        from ..data.batch import ColumnBatch
        from ..table.bucket import bucket_ids
        from ..types import BIGINT, RowType

        rt = RowType.of(("k", BIGINT()))
        start = self.offset
        got: dict[int, list[int]] = {b: [] for b in owned}
        while any(len(v) < per_bucket for v in got.values()):
            ks = np.arange(self.base + self.offset, self.base + self.offset + 2048, dtype=np.int64)
            self.offset += 2048
            bs = bucket_ids(ColumnBatch.from_pydict(rt, {"k": ks}), ["k"], self.num_buckets)
            for b in owned:
                need = per_bucket - len(got[b])
                if need > 0:
                    got[b].extend(ks[bs == b][:need].tolist())
        return got, start, self.offset - start


class ClusterWorkerAgent:
    """One worker's protocol logic, independent of process boundaries so
    tests drive it in-process. The OS-process child (worker_main) wraps one
    around a freshly initialized jax runtime (parallel.distributed.
    init_worker_runtime — multi-host when configured, single-process
    fallback otherwise)."""

    def __init__(
        self,
        wid: int,
        table,
        coord_host: str,
        coord_port: int,
        journal_path: str | None = None,
        incarnation: int = 0,
        serve: bool = True,
        round_rows: int = 256,
        update_fraction: float = 0.3,
        admit_timeout_s: float = 30.0,
        heartbeat_interval_s: float = 0.5,
        seed: int = 0,
        serve_delay_ms: "float | None" = None,
    ):
        from .proc_soak import WriterJournal

        self.wid = wid
        self.table = table
        self.user = f"{ClusterCoordinator.USER_PREFIX}{wid}"
        self.num_buckets = max(table.store.options.bucket, 1)
        self.round_rows = round_rows
        self.update_fraction = update_fraction
        self.admit_timeout_s = admit_timeout_s
        self.heartbeat_interval_s = heartbeat_interval_s
        self.rng = np.random.default_rng(seed * 7919 + wid * 104729 + incarnation)
        self.incarnation = incarnation
        self.conn = _RpcConn(coord_host, coord_port)
        self.route_epoch = 0
        self.server: _WorkerServer | None = None
        if serve:
            self.server = _WorkerServer(
                table, self._owned_set, delay_ms=serve_delay_ms,
                route_epoch=lambda: self.route_epoch,
            )
        self._assign_lock = threading.Lock()
        self._reload_lock = threading.Lock()
        self._epoch = 0
        self._buckets: set[int] = set()
        self._go = False
        self._retire_flag = False
        self.retired = False
        self._stop = threading.Event()
        self._hb_thread: threading.Thread | None = None
        self.journal = None
        self.next_ident = 1
        self.landed_by_bucket: dict[int, list[int]] = {}
        self.keygen = _KeyGen(self.num_buckets, wid * KEYSPACE)
        self.recovered = 0
        if journal_path is not None:
            self.journal = WriterJournal(journal_path)
            self._recover(journal_path)
            self.journal.open()

    # ---- journal recovery (PR 9 machinery, verbatim protocol) ----------
    def _recover(self, journal_path: str) -> None:
        from ..data.batch import ColumnBatch
        from ..table.bucket import bucket_ids
        from ..types import BIGINT, RowType
        from .proc_soak import WriterJournal

        events = WriterJournal.read(journal_path)
        intents = [e for e in events if e["t"] == "intent"]
        resolved = {e["ident"] for e in events if e["t"] in ("ack", "recovered", "abort")}
        acked = {e["ident"] for e in events if e["t"] in ("ack", "recovered")}
        self.next_ident = max((e["ident"] for e in intents), default=0) + 1
        self.keygen.offset = max((e["fresh"][0] + e["fresh"][1] for e in intents), default=0)
        self._pending_recovery = [e for e in intents if e["ident"] not in resolved]
        landed_keys = [int(k) for e in intents if e["ident"] in acked for k in e["rows"]]
        self._landed_pending = landed_keys
        if landed_keys:
            rt = RowType.of(("k", BIGINT()))
            ks = np.asarray(landed_keys, dtype=np.int64)
            bs = bucket_ids(ColumnBatch.from_pydict(rt, {"k": ks}), ["k"], self.num_buckets)
            for k, b in zip(landed_keys, bs.tolist()):
                self.landed_by_bucket.setdefault(int(b), []).append(k)

    def _resolve_unacked(self) -> None:
        """Respawn half of the recovery: every intent without an ack is
        resolved against the SNAPSHOT CHAIN (the coordinator may have
        committed the round after this worker died mid-ship) —
        adopt-never-replay, exactly the PR 9 writer protocol."""
        pending = getattr(self, "_pending_recovery", [])
        self._pending_recovery = []
        for e in pending:
            sid = find_landed_append(self.table.store, self.user, e["ident"])
            if sid is not None:
                self.journal.recovered(e["ident"], sid)
                self.recovered += 1
                from ..data.batch import ColumnBatch
                from ..table.bucket import bucket_ids
                from ..types import BIGINT, RowType

                ks = np.asarray([int(k) for k in e["rows"]], dtype=np.int64)
                if len(ks):
                    rt = RowType.of(("k", BIGINT()))
                    bs = bucket_ids(ColumnBatch.from_pydict(rt, {"k": ks}), ["k"], self.num_buckets)
                    for k, b in zip(ks.tolist(), bs.tolist()):
                        self.landed_by_bucket.setdefault(int(b), []).append(int(k))
            else:
                self.journal.abort(e["ident"])

    # ---- assignment sync -----------------------------------------------
    def _owned_set(self) -> set[int]:
        with self._assign_lock:
            return set(self._buckets)

    def _apply(self, resp: dict) -> None:
        # bucket-count change applies BEFORE the epoch/bucket assignment:
        # by the time a post-rescale epoch is visible to ingest_round, the
        # table, keygen, and serving query already speak the new layout
        # (the reverse order would let a round write old-layout files and
        # ship them under a new epoch — past the fence, wrong total_buckets)
        nb = resp.get("num_buckets")
        if nb is not None and int(nb) != self.num_buckets:
            self._on_bucket_count_change(int(nb))
        with self._assign_lock:
            re = resp.get("route_epoch")
            if re is not None and int(re) > self.route_epoch:
                self.route_epoch = int(re)
            if "epoch" in resp and resp.get("epoch") is not None:
                self._epoch = int(resp["epoch"])
                self._buckets = {int(b) for b in resp.get("buckets", ())}
            self._go = bool(resp.get("go", self._go))
            if resp.get("retire"):
                self._retire_flag = True
            if resp.get("stop"):
                self._stop.set()

    def _on_bucket_count_change(self, n: int) -> None:
        """The coordinator committed a rescale: reload the table at the new
        schema, re-key the fresh-key generator, rebucketize the landed-key
        update pool, and swap the serving plane's query — all before the
        new assignment epoch becomes visible (see _apply)."""
        from ..table import load_table

        with self._reload_lock:
            if n == self.num_buckets:
                return
            table = load_table(str(self.table.path), commit_user=self.user)
            new_map: dict[int, list[int]] = {}
            landed = [k for ks in self.landed_by_bucket.values() for k in ks]
            if landed:
                from ..data.batch import ColumnBatch
                from ..table.bucket import bucket_ids
                from ..types import BIGINT, RowType

                ks = np.asarray(landed, dtype=np.int64)
                bs = bucket_ids(
                    ColumnBatch.from_pydict(RowType.of(("k", BIGINT())), {"k": ks}), ["k"], n
                )
                for k, b in zip(landed, bs.tolist()):
                    new_map.setdefault(int(b), []).append(int(k))
            self.table = table
            self.num_buckets = n
            self.keygen.num_buckets = n
            self.landed_by_bucket = new_map
            if self.server is not None:
                self.server.reload_table(table)

    def assignment(self) -> tuple[int, list[int]]:
        with self._assign_lock:
            return self._epoch, sorted(self._buckets)

    def register(self) -> None:
        kw = {"worker": self.wid, "incarnation": self.incarnation}
        if self.server is not None:
            kw["serve_host"] = self.server.host
            kw["serve_port"] = self.server.port
        self._apply(self.conn.call("register", **kw))
        if self.journal is not None:
            self._resolve_unacked()

    def start_heartbeats(self) -> None:
        if self._hb_thread is not None:
            return

        def loop():
            while not self._stop.wait(self.heartbeat_interval_s):
                kw = {"worker": self.wid, "epoch": self._epoch}
                if self.server is not None:
                    gets = self.server.take_get_counts()
                    if gets:
                        # serve-read heat report: the replica planner's input
                        kw["gets"] = {str(b): n for b, n in gets.items()}
                try:
                    resp = self.conn.call("heartbeat", **kw)
                except Exception:
                    continue  # coordinator shutting down: main loop handles stop
                if resp.get("reregister"):
                    try:
                        self._apply(self.conn.call("register", worker=self.wid,
                                                   incarnation=self.incarnation,
                                                   **({"serve_host": self.server.host,
                                                       "serve_port": self.server.port}
                                                      if self.server else {})))
                    except Exception:
                        pass
                else:
                    self._apply(resp)

        self._hb_thread = threading.Thread(
            target=loop, name=f"paimon-clu-hb-{self.wid}", daemon=True
        )
        self._hb_thread.start()

    # ---- ingest --------------------------------------------------------
    def _admit(self, ident: int, buckets: list[int]) -> bool:
        deadline = time.monotonic() + self.admit_timeout_s
        while not self._stop.is_set():
            r = self.conn.call("admit", worker=self.wid, ident=ident, buckets=buckets)
            if r.get("admitted"):
                return True
            if r.get("rescaling"):
                # the rescale window: stop queueing at the gate and go poll —
                # the rewrite task for our owned buckets is waiting
                return False
            if time.monotonic() >= deadline:
                return False
            time.sleep(min(r.get("retry_after_ms", 100) / 1000.0, 0.25))
        return False

    def ingest_round(self) -> bool:
        """One journaled ingest round over the currently owned buckets:
        admit -> intent -> local mesh flush -> ship -> ack/abort. Returns
        True when the round landed."""
        from ..data.batch import ColumnBatch
        from ..resilience.faults import crash_point
        from ..table.write import TableWrite

        epoch, owned = self.assignment()
        if not owned:
            time.sleep(0.1)
            return False
        ident = self.next_ident
        if not self._admit(ident, owned):
            return False
        self.next_ident += 1
        per_bucket = max(self.round_rows, 1)
        n_upd = int(per_bucket * self.update_fraction)
        fresh, scan_start, scan_span = self.keygen.take(set(owned), per_bucket - n_upd)
        keys: list[int] = []
        for b in owned:
            keys.extend(fresh[b])
            landed = self.landed_by_bucket.get(b, [])
            if landed and n_upd:
                idx = self.rng.integers(0, len(landed), min(n_upd, len(landed)))
                keys.extend(landed[i] for i in idx)
        vals = (ident * 1000.0 + self.wid) + self.rng.random(len(keys))
        rows = dict(zip(keys, (float(v) for v in vals)))
        if self.journal is not None:
            self.journal.intent(ident, scan_start, scan_span, rows)
        tw = TableWrite(self.table)
        try:
            ks = list(rows)
            vs = [rows[k] for k in ks]
            for i in range(0, len(ks), 512):
                tw.write(ColumnBatch.from_pydict(SCHEMA, {"k": ks[i : i + 512], "v": vs[i : i + 512]}))
            msgs = tw.prepare_commit()
        finally:
            tw.close()
        crash_point("cluster:before-ship")
        r = self.conn.call(
            "ship_commit",
            worker=self.wid,
            epoch=epoch,
            ident=ident,
            kind="append",
            messages=[m.to_dict() for m in msgs],
        )
        if r.get("sid") is not None:
            if self.journal is not None:
                self.journal.ack(ident, r["sid"])
            for b in owned:
                self.landed_by_bucket.setdefault(b, []).extend(fresh[b])
            return True
        # stale fence or verifiably-not-landed: the round's files are
        # orphans for the sweep, the keys are never reused
        if self.journal is not None:
            self.journal.abort(ident)
        return False

    # ---- compaction execution ------------------------------------------
    def poll_and_compact(self) -> int:
        epoch, _ = self.assignment()
        r = self.conn.call("poll_work", worker=self.wid, epoch=epoch)
        self._apply(r)
        done = 0
        for task in r.get("tasks", ()):
            if task.get("kind") == "rescale":
                if self._execute_rescale(task):
                    done += 1
            elif self._execute_task(task, epoch):
                done += 1
        return done

    def _execute_rescale(self, task: dict) -> bool:
        """Worker half of the cross-worker rescale: rewrite the owned old
        buckets at the pinned snapshot (merged rows, clustered by new
        bucket id), ship the new-layout CommitMessages under the task's
        fence epoch. The coordinator commits once every old bucket is
        covered; a kill before the ship just re-queues these buckets on
        whoever inherits them."""
        from ..resilience.faults import crash_point
        from ..table.rescale import rescale_messages

        _, msgs, _ = rescale_messages(
            self.table,
            int(task["new_buckets"]),
            buckets=[int(b) for b in task["buckets"]],
            snapshot_id=task.get("snapshot"),
        )
        crash_point("rescale:before-ship")
        r = self.conn.call(
            "ship_commit",
            worker=self.wid,
            epoch=int(task["epoch"]),
            kind="rescale",
            buckets=[int(b) for b in task["buckets"]],
            messages=[m.to_dict() for m in msgs],
        )
        self._apply(r)
        return not r.get("stale")

    def _execute_task(self, task: dict, epoch: int) -> bool:
        """Worker half of the cluster compaction drain: rewrite through the
        local mesh engine, ship the CommitMessage — the coordinator commits
        (or abandons on conflict)."""
        from ..resilience.faults import crash_point
        from ..table.write import TableWrite

        t = self.table.copy(
            {
                "write-only": "false",
                "num-sorted-run.compaction-trigger": str(max(int(task.get("trigger", 3)) - 1, 1)),
            }
        )
        tw = TableWrite(t)
        try:
            tw._writer(tuple(task["partition"]), int(task["bucket"]))
            crash_point("cluster:compact-executing")
            tw.compact(full=bool(task["deep"]))
            msgs = [m for m in tw.prepare_commit() if not m.is_empty()]
        finally:
            tw.close()
        r = self.conn.call(
            "ship_commit",
            worker=self.wid,
            epoch=epoch,
            kind="compact",
            task_id=task["task_id"],
            messages=[m.to_dict() for m in msgs],
        )
        return r.get("sid") is not None

    # ---- loops ----------------------------------------------------------
    def run_serve(self) -> None:
        """Serve-only loop (distributed SQL workers): register, heartbeat,
        answer get_batch / subscribe / join_part / scan_frag until told to
        stop. No ingest — the table is whatever the store already holds."""
        self.register()
        self.start_heartbeats()
        while not self._stop.wait(0.2):
            if self._retire_flag:
                self.retire()
                break

    def run_soak(self) -> None:
        self.register()
        self.start_heartbeats()
        while not self._stop.is_set():
            try:
                if self._retire_flag:
                    self.retire()
                    break
                self.ingest_round()
                self.poll_and_compact()
            except ConnectionError:
                break  # coordinator gone: drain
            except Exception:
                # a lost CAS race surfaced as an error response, an injected
                # fault, etc. — survivable, re-observe and continue
                time.sleep(0.05)

    def retire(self) -> None:
        """Planned scale-in drain: called BETWEEN rounds, so every shipped
        round is settled and nothing is in flight — the retire RPC hands the
        range off through the reassignment machinery (a death without the
        timeout) and this process exits clean. A kill at the crash point
        degrades to exactly the missed-heartbeat path: same handoff, later."""
        from ..resilience.faults import crash_point

        crash_point("handoff:before-retire")
        try:
            self.conn.call("retire", worker=self.wid)
        except Exception:  # noqa: BLE001 — coordinator gone: drain anyway
            pass
        self.retired = True
        self._stop.set()

    def wait_go(self, timeout_s: float = 120.0) -> None:
        deadline = time.monotonic() + timeout_s
        while not self._go and time.monotonic() < deadline and not self._stop.is_set():
            time.sleep(0.05)

    def barrier(self, name: str, expected: int, timeout_s: float = 300.0) -> None:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            r = self.conn.call("barrier", worker=self.wid, name=name, expected=expected)
            if r.get("released"):
                return
            time.sleep(0.05)
        raise TimeoutError(f"barrier {name} not released")

    def close(self) -> None:
        self._stop.set()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=10.0)
            self._hb_thread = None
        if self.server is not None:
            self.server.close()
            self.server = None
        if self.journal is not None:
            self.journal.close()
        self.conn.close()


# ---------------------------------------------------------------------------
# routed client: get_batch / subscribe / join partitions across workers
# ---------------------------------------------------------------------------
class ClusterClient:
    """Client-side routing over the coordinator's bucket->worker table.

    * get_batch: probe keys bucketize with the table's own hash, each
      owner-worker serves its group in one vectorized probe, results
      reassemble in probe order — the PR 13 serving path, now spanning
      worker processes.
    * subscribe: one filtered subscription per owning worker; each worker
      fans only the rows of the requested buckets (the PR 14 follow-up).
    * join partitions: `partition_executor()` returns the seam ops.join
      installs — JSPIM partition i routes to the worker owning bucket
      (i % num_buckets), so the skew split spans workers."""

    def __init__(self, table, coord_host: str, coord_port: int):
        self.table = table
        self.num_buckets = max(table.store.options.bucket, 1)
        self._coord = _RpcConn(coord_host, coord_port)
        self._conns: dict[int, _RpcConn] = {}
        self._route: dict[int, int] = {}
        self._addrs: dict[int, tuple[str, int]] = {}
        self._replicas: dict[int, list[int]] = {}
        self._route_lock = threading.Lock()
        self.route_epoch = 0
        self._route_dirty = False
        self._rr = 0
        self.refresh_route()

    def refresh_route(self) -> None:
        r = self._coord.call("route")
        route: dict[int, int] = {}
        addrs: dict[int, tuple[str, int]] = {}
        for wid_s, info in r["workers"].items():
            wid = int(wid_s)
            if info.get("port") is None:
                continue
            addrs[wid] = (info["host"], info["port"])
            for b in info["buckets"]:
                route[int(b)] = wid
        replicas = {
            int(b): [int(w) for w in wids if int(w) in addrs]
            for b, wids in (r.get("replicas") or {}).items()
        }
        self._route, self._addrs = route, addrs
        self._replicas = {b: ws for b, ws in replicas.items() if ws}
        self.num_buckets = int(r.get("num_buckets", self.num_buckets))
        with self._route_lock:
            e = int(r.get("route_epoch", 0))
            if e > self.route_epoch:
                self.route_epoch = e
            self._route_dirty = False
        for wid in list(self._conns):
            if wid not in addrs:
                self._conns.pop(wid).close()

    def note_route_epoch(self, epoch: int) -> None:
        """Push-based invalidation sink: every RPC reply (coordinator or
        worker serving plane) carries the route epoch; a bump marks the
        cached route dirty, and the next routing decision refreshes —
        clients learn about rescales/reassignments/replica changes without
        waiting for a rejected call."""
        with self._route_lock:
            if epoch > self.route_epoch:
                self.route_epoch = epoch
                self._route_dirty = True

    def _maybe_refresh(self) -> None:
        with self._route_lock:
            dirty = self._route_dirty
        if dirty:
            self.refresh_route()

    def _call(self, wid: int, method: str, **kw) -> dict:
        """Worker RPC with the route-epoch sniff on the reply."""
        r = self._conn(wid).call(method, **kw)
        e = r.get("route_epoch")
        if e is not None:
            self.note_route_epoch(int(e))
        return r

    def _conn(self, wid: int) -> _RpcConn:
        conn = self._conns.get(wid)
        if conn is None:
            conn = self._conns[wid] = _RpcConn(*self.addr_of(wid))
        return conn

    def replicas_of(self, bucket: int) -> list[int]:
        """Live replica owners of a bucket (primaries excluded) — the
        gateway's replica-first hedge pool."""
        self._maybe_refresh()
        return [w for w in self._replicas.get(int(bucket), ()) if w in self._addrs]

    def serving_owner_of(self, bucket: int) -> int:
        """Read routing: round-robin over the primary plus every live
        replica (a hot bucket's gets spread across its owner set); writes
        and compaction stay primary-only, so this is only ever used on the
        serving plane where any owner answers bit-identically off shared
        FS."""
        primary = self.owner_of(bucket)
        reps = [w for w in self._replicas.get(int(bucket), ()) if w != primary and w in self._addrs]
        if not reps:
            return primary
        ring = [primary, *reps]
        with self._route_lock:
            self._rr += 1
            pick = ring[self._rr % len(ring)]
        if pick != primary:
            from ..metrics import cluster_metrics

            cluster_metrics().counter("replica_reads").inc()
        return pick

    def owner_of(self, bucket: int) -> int:
        """The worker serving a bucket's reads. Every consumer (routed
        gets, scan fragments, subscribe fan-in, join partitions) reads the
        shared filesystem, so a bucket whose owner died and has not
        re-registered falls back to any live worker — bit-identical answer,
        no window where a respawn surfaces as a raw KeyError. With nothing
        live at all the escape is ConnectionError, which every dispatch
        failover loop already absorbs."""
        self._maybe_refresh()
        if bucket not in self._route:
            self.refresh_route()
        wid = self._route.get(bucket)
        if wid is not None:
            return wid
        live = sorted(self._addrs)
        if live:
            return live[bucket % len(live)]
        raise ConnectionError(f"no live worker serves bucket {bucket}")

    def drop_conn(self, wid: int) -> None:
        """Forget a worker's cached connection (the failover path: the next
        fragment for its buckets reconnects through a refreshed route)."""
        conn = self._conns.pop(wid, None)
        if conn is not None:
            conn.close()

    def live_workers(self) -> list[int]:
        """Worker ids with a serving address under the current route — the
        gateway's hedge-secondary candidate pool (any live worker serves
        get_batch/scan_frag from the shared filesystem, owner or not)."""
        return sorted(self._addrs)

    def addr_of(self, wid: int) -> "tuple[str, int]":
        """A worker's serving address. A wid the route advertised a moment
        ago can vanish under a concurrent refresh (the respawn window) —
        that is a dead route, ConnectionError, never a KeyError escaping
        through a dispatch path that only absorbs connection-grain faults."""
        try:
            return self._addrs[wid]
        except KeyError:
            raise ConnectionError(f"worker {wid} has no serving address") from None

    # ---- distributed SQL scan fragments (ISSUE 16) ----------------------
    def scan_frag(self, wid: int, frag: dict, busy_wait_s: float = 10.0) -> dict:
        """Execute one wire-encoded scan fragment on worker `wid`, absorbing
        typed-BUSY sheds with the server-advertised retry_after backoff.
        Raises ConnectionError/RuntimeError like every other worker call —
        the planner's failover loop owns re-dispatch."""
        deadline = time.monotonic() + busy_wait_s
        while True:
            r = self._call(wid, "scan_frag", frag=frag)
            if not r.get("busy"):
                return r["partial"]
            if time.monotonic() >= deadline:
                raise TimeoutError(f"worker {wid} still BUSY after {busy_wait_s}s")
            time.sleep(float(r.get("retry_after_ms", 50)) / 1000.0)

    # ---- shuffle exchange (ISSUE 20) ------------------------------------
    def exchange_combine(
        self,
        wid: int,
        qid: str,
        rng: int,
        expect: list,
        group_cols,
        kern,
        engine: str,
        code_domain: bool,
        projection,
        busy_wait_s: float = 10.0,
    ) -> "tuple[dict | None, list]":
        """Ask range owner `wid` to fold the expected parts of range `rng`
        into one reduced partial. Returns (wire partial, []) on success or
        (None, missing srcs) when the owner's inbound buffer has gaps —
        the coordinator reships those and retries. BUSY absorbs with the
        advertised backoff like scan_frag."""
        deadline = time.monotonic() + busy_wait_s
        while True:
            r = self._call(
                wid,
                "exchange_combine",
                qid=qid,
                rng=int(rng),
                expect=list(expect),
                group_cols=list(group_cols),
                kern=[list(k) for k in kern],
                engine=engine,
                code_domain=bool(code_domain),
                projection=None if projection is None else list(projection),
            )
            if r.get("busy"):
                if time.monotonic() >= deadline:
                    raise TimeoutError(f"worker {wid} still BUSY after {busy_wait_s}s")
                time.sleep(float(r.get("retry_after_ms", 50)) / 1000.0)
                continue
            if r.get("missing") is not None:
                return None, list(r["missing"])
            return r["partial"], []

    def exchange_reship(self, wid: int, qid: str, rng: int, src: str, host: str, port: int) -> bool:
        """Ask source worker `wid` to re-send its buffered part for
        (qid, rng, src) to the range's current owner at host:port. False on
        any failure (source dead, buffer gone, delivery failed) — the
        caller's escalation (re-execute the fragment) is uniform."""
        try:
            r = self._call(
                wid, "exchange_reship", qid=qid, rng=int(rng), src=src, host=host, port=int(port)
            )
        except (ConnectionError, OSError, TimeoutError, RuntimeError):
            self.drop_conn(wid)
            return False
        if r.get("busy"):
            return False
        return bool(r.get("shipped"))

    def exchange_close(self, qid: str, wids) -> None:
        """Best-effort buffer release on every worker a shuffle touched;
        the worker-side TTL GC covers whatever this misses."""
        for wid in wids:
            try:
                self._call(wid, "exchange_close", qid=qid)
            except Exception:  # noqa: BLE001 — cleanup must never fail a query
                pass

    # ---- batched gets ---------------------------------------------------
    def get_batch(self, keys, partition: tuple = ()) -> list:
        """list[tuple | None] aligned with `keys`, each group served by the
        worker owning its bucket."""
        from ..data.batch import ColumnBatch
        from ..table.bucket import bucket_ids

        store = self.table.store
        ks = [k if isinstance(k, tuple) else (k,) for k in keys]
        key_schema = store.value_schema.project(store.key_names)
        probe = ColumnBatch.from_pydict(
            key_schema,
            {name: [k[i] for k in ks] for i, name in enumerate(store.key_names)},
        )
        buckets = bucket_ids(probe, self.table.schema.bucket_keys, self.num_buckets)
        out: list = [None] * len(ks)
        by_wid: dict[int, list[int]] = {}
        for i, b in enumerate(buckets.tolist()):
            by_wid.setdefault(self.serving_owner_of(int(b)), []).append(i)
        for wid, idxs in by_wid.items():
            try:
                rows = self._call(
                    wid,
                    "get_batch",
                    keys=[list(ks[i]) for i in idxs],
                    partition=list(partition),
                )["rows"]
            except ConnectionError:
                # the picked owner (typically a replica) died mid-read: one
                # failover pass through the refreshed primaries — a second
                # failure escapes like any other dead route
                self.drop_conn(wid)
                self.refresh_route()
                retry: dict[int, list[int]] = {}
                for i in idxs:
                    retry.setdefault(self.owner_of(int(buckets[i])), []).append(i)
                for w2, idxs2 in retry.items():
                    rows2 = self._call(
                        w2,
                        "get_batch",
                        keys=[list(ks[i]) for i in idxs2],
                        partition=list(partition),
                    )["rows"]
                    for i, row in zip(idxs2, rows2):
                        out[i] = None if row is None else tuple(row)
                continue
            for i, row in zip(idxs, rows):
                out[i] = None if row is None else tuple(row)
        return out

    # ---- routed subscriptions -------------------------------------------
    def subscribe(self, buckets: "list[int] | None" = None, from_snapshot: int | None = None):
        """[(wid, handle)] per owning worker; each handle's poll() returns
        {rows, snapshot_id, checkpoint} filtered to that worker's share of
        `buckets` (all buckets when None)."""
        self._maybe_refresh()
        want = list(range(self.num_buckets)) if buckets is None else [int(b) for b in buckets]
        by_wid: dict[int, list[int]] = {}
        for b in want:
            by_wid.setdefault(self.serving_owner_of(b), []).append(b)
        handles = []
        for wid, bs in by_wid.items():
            conn = self._conn(wid)
            sub_id = conn.call(
                "subscribe_open", buckets=bs, from_snapshot=from_snapshot
            )["sub_id"]
            handles.append((wid, _RoutedSubscription(conn, sub_id)))
        return handles

    # ---- distributed join partitions ------------------------------------
    def partition_executor(self):
        """The ops.join.partition_executor seam: partition i runs on the
        worker owning bucket (i % num_buckets)."""

        def run(parts):
            out = []
            for i, (ll, rl, algorithm, engine) in enumerate(parts):
                wid = self.owner_of(i % self.num_buckets)
                r = self._call(
                    wid,
                    "join_part",
                    ll=_b64(np.asarray(ll, dtype=np.uint32)),
                    rl=_b64(np.asarray(rl, dtype=np.uint32)),
                    algorithm=algorithm,
                    engine=engine,
                )
                out.append((_unb64(r["lt"]), _unb64(r["rt"])))
            return out

        return run

    def close(self) -> None:
        for conn in self._conns.values():
            conn.close()
        self._coord.close()


class _RoutedSubscription:
    def __init__(self, conn: _RpcConn, sub_id: str):
        self._conn = conn
        self.sub_id = sub_id

    def poll(self, timeout_ms: int = 1000) -> dict:
        return self._conn.call("subscribe_poll", sub_id=self.sub_id, timeout_ms=timeout_ms)

    def close(self, delete_consumer: bool = False) -> None:
        try:
            self._conn.call("subscribe_close", sub_id=self.sub_id, delete_consumer=delete_consumer)
        except Exception:
            pass


# ---------------------------------------------------------------------------
# supervisor: spawn/kill/respawn workers, run the coordinator, verify
# ---------------------------------------------------------------------------
class ClusterSupervisor:
    """The PR 9 supervisor shape around a live coordinator: worker OS
    processes are spawned (crash-point armed through the environment),
    SIGKILLed on a seeded timer and at scripted points (including
    mid-compaction), respawned and journal-recovered; the coordinator
    reassigns orphaned bucket ranges on missed heartbeats. End-of-soak
    verification is the proc-soak oracle verbatim: fold of landed rounds ==
    final scan, total_record_count == unique keys, zero leaked files after
    the threshold-0 sweep — plus the cluster's own gate: sampled read-amp
    p99 never passed the adaptive ceiling."""

    def __init__(self, base_dir: str, cfg: ClusterConfig | None = None):
        self.cfg = cfg or ClusterConfig()
        self.base_dir = str(base_dir)
        self.table_root = os.path.join(self.base_dir, "cluster_table")
        self.run_dir = os.path.join(self.base_dir, "cluster_run")
        self.stop_file = os.path.join(self.run_dir, "stop")
        self.coordinator: ClusterCoordinator | None = None
        self.errors: list[str] = []
        self.inconsistencies: list[dict] = []
        self.read_amp_samples: list[float] = []
        self.counts = {
            "procs_spawned": 0,
            "procs_killed": 0,
            "procs_respawned": 0,
            "worker_errors": 0,
            "sweeps_during_soak": 0,
            "workers_admitted": 0,
            "workers_retired": 0,
            "rescales_requested": 0,
        }
        self._kill_cursor = 0
        self._incarnations: dict[tuple, int] = {}
        self._retiring_wids: set[int] = set()
        self._spawned_wids: set[int] = set()

    # ---- setup ---------------------------------------------------------
    def _table_options(self) -> dict:
        cfg = self.cfg
        opts = {
            "bucket": str(cfg.buckets),
            "write-only": "true",  # compaction belongs to the cluster service
            "merge.engine": "mesh",
            "write-buffer-rows": str(max(cfg.round_rows, 64)),
            "commit.max-retries": "30",
            "commit.retry-backoff": "2 ms",
            "cluster.workers": str(cfg.workers),
            "cluster.devices-per-worker": str(cfg.devices_per_worker),
            "compaction.adaptive.read-amp-ceiling": str(cfg.read_amp_ceiling),
            "compaction.adaptive.interval": "300 ms",
            "compaction.adaptive.max-buckets-per-round": "2",
        }
        opts.update(cfg.table_options)
        return opts

    def setup(self) -> None:
        from ..core.schema import SchemaManager
        from ..fs import get_file_io

        os.makedirs(self.run_dir, exist_ok=True)
        io = get_file_io(self.table_root)
        SchemaManager(io, self.table_root).create_table(
            SCHEMA, primary_keys=["k"], options=self._table_options()
        )

    def _fresh_table(self):
        from ..table import load_table

        return load_table(self.table_root, commit_user="cluster-supervisor")

    # ---- children ------------------------------------------------------
    def _child_env(self, crash_spec: str | None, devices: int) -> dict:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        flags = " ".join(
            f for f in flags.split() if not f.startswith("--xla_force_host_platform_device_count")
        )
        env["XLA_FLAGS"] = (flags + f" --xla_force_host_platform_device_count={devices}").strip()
        env["PAIMON_TPU_CLUSTER_ROLE"] = "worker"
        env.pop("PAIMON_TPU_CRASH_POINT", None)
        if crash_spec:
            env["PAIMON_TPU_CRASH_POINT"] = crash_spec
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = repo_root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
        return env

    def _spawn_worker(self, wid: int) -> subprocess.Popen:
        from ..metrics import soak_metrics

        cfg = self.cfg
        crash_spec = None
        if self._kill_cursor < len(cfg.scripted_kills):
            crash_spec = cfg.scripted_kills[self._kill_cursor]
            self._kill_cursor += 1
        inc = self._incarnations.get(("w", wid), 0)
        self._incarnations[("w", wid)] = inc + 1
        self._spawned_wids.add(wid)
        log = open(os.path.join(self.run_dir, f"worker-{wid}.{inc}.log"), "wb")
        cmd = [
            sys.executable, "-m", "paimon_tpu.service.cluster", "worker",
            "--table", self.table_root,
            "--wid", str(wid),
            "--coordinator", f"{self.coordinator.host}:{self.coordinator.port}",
            "--journal", os.path.join(self.run_dir, f"journal-{wid}.jsonl"),
            "--incarnation", str(inc),
            "--seed", str(cfg.seed),
            "--round-rows", str(cfg.round_rows),
            "--devices", str(cfg.devices_per_worker),
            "--admit-timeout", str(cfg.admit_timeout_s),
            "--heartbeat-interval", str(cfg.heartbeat_interval_s),
        ]
        if not cfg.serve:
            cmd.append("--no-serve")
        p = subprocess.Popen(
            cmd, stdout=log, stderr=subprocess.STDOUT,
            env=self._child_env(crash_spec, cfg.devices_per_worker),
        )
        log.close()
        self.counts["procs_spawned"] += 1
        soak_metrics().counter("procs_spawned").inc()
        return p

    def _spawn_reader(self, rid: int) -> subprocess.Popen:
        inc = self._incarnations.get(("r", rid), 0)
        self._incarnations[("r", rid)] = inc + 1
        log = open(os.path.join(self.run_dir, f"reader-{rid}.{inc}.log"), "wb")
        cmd = [
            sys.executable, "-m", "paimon_tpu.service.cluster", "reader",
            "--table", self.table_root,
            "--rid", str(rid),
            "--log", os.path.join(self.run_dir, f"reads-{rid}.jsonl"),
            "--stop-file", self.stop_file,
        ]
        p = subprocess.Popen(
            cmd, stdout=log, stderr=subprocess.STDOUT, env=self._child_env(None, 1)
        )
        log.close()
        self.counts["procs_spawned"] += 1
        return p

    def _elastic_event(self, act: str, arg: "int | None", workers: dict) -> None:
        """One scripted elastic action against the live fleet: a rescale
        (coordinator-driven, under load), a worker admit (fresh wid beyond
        the home split — the register steal path plans its range handoff),
        or a retire (coordinator drain flag; the clean rc=0 exit is removed
        from the fleet instead of respawned)."""
        if act == "rescale":
            new_n = arg if arg else self.coordinator.num_buckets * 2
            r = self.coordinator.start_rescale(new_n)
            if r.get("started"):
                self.counts["rescales_requested"] += 1
        elif act == "admit":
            wid = (max(workers) + 1) if workers else self.cfg.workers
            workers[wid] = self._spawn_worker(wid)
            self.counts["workers_admitted"] += 1
        elif act == "retire":
            live = [
                w
                for w in sorted(workers)
                if workers[w].poll() is None and w not in self._retiring_wids
            ]
            if len(live) > 1:  # never retire the last worker
                wid = live[-1]  # highest wid: the admitted joiner when present
                self.coordinator.request_retire(wid)
                self._retiring_wids.add(wid)
        else:
            raise ValueError(f"unknown elastic action {act!r}")

    def _reap(self, role: str, idx: int, rc: int) -> None:
        from ..metrics import soak_metrics
        from ..resilience.faults import KILL_EXIT_CODE

        if rc == KILL_EXIT_CODE or rc < 0:
            self.counts["procs_killed"] += 1
            soak_metrics().counter("procs_killed").inc()
        elif rc != 0:
            self.counts["worker_errors"] += 1
            inc = self._incarnations.get((role[0], idx), 1) - 1
            log = os.path.join(self.run_dir, f"{role}-{idx}.{inc}.log")
            tail = ""
            if os.path.exists(log):
                with open(log, "rb") as f:
                    tail = f.read()[-2000:].decode(errors="replace")
            self.errors.append(f"{role} {idx} exited rc={rc}:\n{tail}")

    # ---- run -----------------------------------------------------------
    def run(self) -> dict:
        from ..metrics import compaction_metrics
        from ..resilience.orphan import remove_orphan_files

        cfg = self.cfg
        if not os.path.exists(self.table_root):
            self.setup()
        os.makedirs(self.run_dir, exist_ok=True)
        self.coordinator = ClusterCoordinator(self.table_root, cfg).start()
        rng = np.random.default_rng(cfg.seed * 31 + 17)
        t_start = time.monotonic()
        deadline = t_start + cfg.duration_s
        workers = {w: self._spawn_worker(w) for w in range(cfg.workers)}
        readers = {r: self._spawn_reader(r) for r in range(cfg.readers)}
        next_kill = (
            t_start + float(rng.uniform(0.5, 1.5)) * cfg.kill_period_s
            if cfg.kill_period_s > 0
            else float("inf")
        )
        next_sweep = t_start + cfg.sweep_period_s if cfg.sweep_period_s > 0 else float("inf")
        # scripted elastic plan: (absolute time, action, arg), time-ordered
        elastic = sorted(
            (
                t_start + float(ev[1]) * cfg.duration_s,
                str(ev[0]),
                int(ev[2]) if len(ev) > 2 and ev[2] is not None else None,
            )
            for ev in cfg.elastic
        )
        gauge = compaction_metrics().gauge("read_amplification_p99")
        while time.monotonic() < deadline:
            for wid, p in list(workers.items()):
                rc = p.poll()
                if rc is None:
                    continue
                if rc == 0 and wid in self._retiring_wids:
                    # planned retire completed its handoff: remove, never
                    # respawn — the range already moved to the survivors
                    del workers[wid]
                    self.counts["workers_retired"] += 1
                    continue
                self._reap("worker", wid, rc)
                workers[wid] = self._spawn_worker(wid)
                self.counts["procs_respawned"] += 1
            for rid, p in list(readers.items()):
                rc = p.poll()
                if rc is None:
                    continue
                self._reap("reader", rid, rc)
                readers[rid] = self._spawn_reader(rid)
                self.counts["procs_respawned"] += 1
            now = time.monotonic()
            while elastic and now >= elastic[0][0]:
                _, act, arg = elastic.pop(0)
                try:
                    self._elastic_event(act, arg, workers)
                except Exception:
                    self.errors.append(f"elastic {act} failed:\n{traceback.format_exc()}")
            if now >= next_kill and workers:
                wids = sorted(workers)
                victim = workers[wids[int(rng.integers(0, len(wids)))]]
                if victim.poll() is None:
                    victim.kill()
                next_kill = now + float(rng.uniform(0.5, 1.5)) * cfg.kill_period_s
            if now >= next_sweep:
                try:
                    remove_orphan_files(
                        self._fresh_table(), older_than_millis=cfg.sweep_older_than_ms
                    )
                    self.counts["sweeps_during_soak"] += 1
                except Exception:
                    self.errors.append(f"mid-soak sweep crashed:\n{traceback.format_exc()}")
                next_sweep = now + cfg.sweep_period_s
            v = getattr(gauge, "value", None)
            if v:
                self.read_amp_samples.append(float(v))
            time.sleep(0.15)
        # ---- drain -----------------------------------------------------
        self.coordinator.stop_event.set()  # workers see stop via heartbeat
        with open(self.stop_file, "w") as f:
            f.write("stop")  # readers poll the file
        drain_deadline = time.monotonic() + 90.0
        procs = [("worker", w, p) for w, p in workers.items()] + [
            ("reader", r, p) for r, p in readers.items()
        ]
        for role, idx, p in procs:
            timeout = max(1.0, drain_deadline - time.monotonic())
            try:
                rc = p.wait(timeout=timeout)
                if rc not in (0, None):
                    self._reap(role, idx, rc)
            except subprocess.TimeoutExpired:
                self.errors.append(f"{role} {idx} failed to drain; killed")
                p.kill()
                p.wait(timeout=30)
        wall_s = time.monotonic() - t_start
        self.coordinator.close()
        return self._verify(wall_s)

    # ---- verification --------------------------------------------------
    def _verify(self, wall_s: float) -> dict:
        from .oracle import fold_landed_rounds, read_client_logs, verify_table_state

        table = self._fresh_table()
        journal_wids = sorted(self._spawned_wids) or list(range(self.cfg.workers))
        landed, stats = fold_landed_rounds(
            table.store,
            {
                f"{ClusterCoordinator.USER_PREFIX}{wid}": os.path.join(
                    self.run_dir, f"journal-{wid}.jsonl"
                )
                for wid in journal_wids
            },
            user_prefix=ClusterCoordinator.USER_PREFIX,
            inconsistencies=self.inconsistencies,
        )
        expected: dict = {}
        for sid in sorted(landed):
            expected.update(landed[sid])
        state = verify_table_state(
            table,
            expected,
            self.table_root,
            self.errors,
            self.inconsistencies,
            force_writable=True,  # lift write-only=true for the final compact
        )
        reads = read_client_logs(
            [os.path.join(self.run_dir, f"reads-{rid}.jsonl") for rid in range(self.cfg.readers)]
        )
        if stats["double_applied"]:
            self.inconsistencies.append({"kind": "double-applied", "rounds": stats["double_applied"]})
        read_amp_max = max(self.read_amp_samples) if self.read_amp_samples else None
        consistent = (
            not self.errors
            and not self.inconsistencies
            and state["lost_rows"] == 0
            and state["duplicated_rows"] == 0
            and state["wrong_values"] == 0
            and reads["read_errors"] == 0
            and state["record_count_matches"]
            and len(state["leaked_files"]) == 0
            and (read_amp_max is None or read_amp_max <= self.cfg.read_amp_ceiling)
        )
        from ..metrics import cluster_metrics

        g = cluster_metrics()
        cluster_counts = {
            k: g.counter(k).count
            for k in (
                "workers_registered",
                "rounds_committed",
                "commits_rejected_stale",
                "reassignments",
                "compact_tasks",
                "compact_commits",
                "compact_conflicts",
                "admit_denied",
                "charges_released",
                "rescales",
                "rescale_aborts",
                "handoffs",
                "replica_reads",
            )
        }
        return {
            "wall_s": round(wall_s, 2),
            "consistent": consistent,
            "final_buckets": table.store.options.bucket,
            "accepted_commits": len(landed),
            "expected_unique_keys": len(expected),
            "final_rows": state["final_rows"],
            "total_record_count": state["total_record_count"],
            "lost_rows": state["lost_rows"],
            "duplicated_rows": state["duplicated_rows"],
            "wrong_values": state["wrong_values"],
            "commits_per_sec": round(len(landed) / wall_s, 2) if wall_s > 0 else None,
            "read_amp_p99_max": read_amp_max,
            "read_amp_ceiling": self.cfg.read_amp_ceiling,
            **stats,
            **self.counts,
            **reads,
            "cluster": cluster_counts,
            "orphans_removed": state["orphans_removed"],
            "leaked_file_count": len(state["leaked_files"]),
            "leaked_files": state["leaked_files"][:10],
            "inconsistencies": self.inconsistencies[:10],
            "errors": self.errors[:5],
        }


def run_cluster_soak(base_dir: str, cfg: ClusterConfig | None = None) -> dict:
    """Create a fresh cluster table under base_dir, run the supervisor
    (coordinator + worker/reader OS processes + kills), return the report."""
    return ClusterSupervisor(base_dir, cfg).run()


# ---------------------------------------------------------------------------
# worker child process
# ---------------------------------------------------------------------------
def worker_main(args) -> int:
    import jax

    from ..parallel import distributed
    from ..table import load_table
    from ..utils import enable_compile_cache

    enable_compile_cache()

    if args.table.startswith(("fail:", "fail-s3", "latency:", "traceable:", "chaos:")):
        # test-harness schemes register on import (the chaos scheme also
        # applies PAIMON_TPU_CHAOS, so this worker inherits the store shape)
        from ..fs import testing as _testing  # noqa: F401
    if args.rtt_read_ms or args.rtt_write_ms:
        from ..fs.testing import LatencyFileIO

        LatencyFileIO.configure(read_ms=args.rtt_read_ms, write_ms=args.rtt_write_ms)
    # the worker startup path runs through the multi-host module —
    # single-process fallback here, the real jax.distributed join when a
    # pod topology is configured; the mesh it returns is the same one the
    # mesh executor will span (parallel.mesh.make_mesh over jax.devices())
    distributed.init_worker_runtime()
    assert not distributed.is_commit_coordinator(), "workers never commit"
    if args.devices:
        assert len(jax.devices()) == args.devices, (len(jax.devices()), args.devices)
    host, port = args.coordinator.rsplit(":", 1)
    table = load_table(args.table, commit_user=f"{ClusterCoordinator.USER_PREFIX}{args.wid}")
    agent = ClusterWorkerAgent(
        args.wid,
        table,
        host,
        int(port),
        journal_path=args.journal,
        incarnation=args.incarnation,
        serve=args.serve,
        round_rows=args.round_rows,
        admit_timeout_s=args.admit_timeout,
        heartbeat_interval_s=args.heartbeat_interval,
        seed=args.seed,
    )
    try:
        if args.mode == "soak":
            agent.run_soak()
        elif args.mode == "serve":
            agent.run_serve()
        else:
            _run_bench_worker(agent, args)
    finally:
        agent.close()
    return 0


def _run_bench_worker(agent: "ClusterWorkerAgent", args) -> None:
    """Bench mode: deterministic per-bucket rounds (independent of worker
    count — the single-process oracle writes the identical rows), a barrier
    so nobody's timed merge-read sees a moving table, then cold merge-read
    passes over the owned shard, each pass asserting a stable digest."""
    import hashlib

    from ..utils.cache import data_file_cache

    from ..data.batch import ColumnBatch
    from ..table.write import TableWrite

    agent.register()
    agent.start_heartbeats()
    agent.wait_go()
    pools = bucket_key_pools(agent.num_buckets, 0, args.round_rows)
    epoch, owned = agent.assignment()

    # ONE long-lived TableWrite across rounds (the reference's streaming
    # writers survive checkpoints): per-round writer re-creation would
    # re-restore sequence state from manifests over the store RTT
    tw = TableWrite(agent.table)

    def ingest_round(r: int) -> int:
        ks: list[int] = []
        for b in owned:
            ks.extend(pools[b].tolist())
        vs = [float(r * 1000 + (k % 997)) for k in ks]
        tw.write(ColumnBatch.from_pydict(SCHEMA, {"k": ks, "v": vs}))
        msgs = tw.prepare_commit()
        resp = agent.conn.call(
            "ship_commit", worker=agent.wid, epoch=epoch, ident=r + 1,
            kind="append", messages=[m.to_dict() for m in msgs],
        )
        assert resp.get("sid") is not None, f"bench round {r} did not land: {resp}"
        return len(ks)

    def plan_owned():
        rb = agent.table.new_read_builder()
        return rb, [s for s in rb.new_scan().plan() if s.bucket in owned]

    def read_pass(planned=None):
        # plan once per phase, read many: the serving layer's refresh()
        # diff keeps plans cached exactly like this — re-planning every
        # pass would measure metadata RTT, not merge-read scaling
        data_file_cache().clear()  # cold data bytes every pass
        rb, splits = planned if planned is not None else plan_owned()
        out = rb.new_read().read_all(splits)
        ks = np.asarray(out.column("k").values)
        vs = np.asarray(out.column("v").values)
        order = np.argsort(ks)
        return out.num_rows, hashlib.sha256(ks[order].tobytes() + vs[order].tobytes()).hexdigest()

    # warm round 0 + one warm read: jit compiles (flush + merge kernels) and
    # the plan's manifest RTT stay out of the timed window — every worker
    # count pays them identically, the bench measures steady-state scaling
    ingest_round(0)
    read_pass()
    agent.barrier("warm", expected=args.expected_workers)
    t0 = time.perf_counter()
    ingested = sum(ingest_round(r) for r in range(1, args.rounds + 1))
    t_ingest = time.perf_counter()
    agent.barrier("ingest", expected=args.expected_workers)
    t_barrier = time.perf_counter()
    rows_read = 0
    digest = None
    planned = plan_owned()
    for _ in range(args.read_iters):
        n, d = read_pass(planned)
        assert digest is None or digest == d, "merge-read digest changed between passes"
        digest = d
        rows_read += n
    wall = time.perf_counter() - t0
    tw.close()
    agent.conn.call(
        "worker_done",
        worker=agent.wid,
        stats={
            "ingested": ingested,
            "rows_read": rows_read,
            "digest": digest,
            "buckets": list(owned),
            "wall_s": wall,
            "ingest_s": round(t_ingest - t0, 3),
            "barrier_s": round(t_barrier - t_ingest, 3),
            "read_s": round(wall - (t_barrier - t0), 3),
        },
    )


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def _worker_args(argv):
    import argparse

    ap = argparse.ArgumentParser(prog="cluster worker")
    ap.add_argument("--table", required=True)
    ap.add_argument("--wid", type=int, required=True)
    ap.add_argument("--coordinator", required=True, help="host:port")
    ap.add_argument("--journal", default=None)
    ap.add_argument("--incarnation", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--round-rows", type=int, default=256, dest="round_rows")
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--admit-timeout", type=float, default=30.0, dest="admit_timeout")
    ap.add_argument("--heartbeat-interval", type=float, default=0.5, dest="heartbeat_interval")
    ap.add_argument("--no-serve", action="store_false", dest="serve")
    ap.add_argument("--mode", choices=("soak", "bench", "serve"), default="soak")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--read-iters", type=int, default=4, dest="read_iters")
    ap.add_argument("--expected-workers", type=int, default=1, dest="expected_workers")
    ap.add_argument("--rtt-read-ms", type=float, default=0.0, dest="rtt_read_ms")
    ap.add_argument("--rtt-write-ms", type=float, default=0.0, dest="rtt_write_ms")
    return ap.parse_args(argv)


def main(argv: list[str] | None = None) -> int:
    import argparse
    import tempfile

    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == "worker":
        return worker_main(_worker_args(argv[1:]))
    if argv and argv[0] == "reader":
        from .proc_soak import _reader_args, reader_main

        return reader_main(_reader_args(argv[1:]))

    ap = argparse.ArgumentParser(description="paimon-tpu cluster soak (coordinator + workers)")
    ap.add_argument("base_dir", nargs="?", default=None)
    ap.add_argument("--duration", type=float, default=45.0)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--devices-per-worker", type=int, default=2)
    ap.add_argument("--readers", type=int, default=1)
    ap.add_argument("--buckets", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--scripted-kills",
        default=",".join(DEFAULT_CLUSTER_KILLS),
        help="comma-separated PAIMON_TPU_CRASH_POINT specs, one per worker spawn",
    )
    ap.add_argument("--kill-period", type=float, default=10.0)
    ap.add_argument("--sweep-period", type=float, default=15.0)
    ap.add_argument("--round-rows", type=int, default=256)
    ap.add_argument("--read-amp-ceiling", type=int, default=10)
    ap.add_argument("--min-kills", type=int, default=0)
    ap.add_argument("--no-compaction", action="store_false", dest="compaction")
    ap.add_argument(
        "--elastic-script",
        default="",
        help=(
            "comma-separated elastic events action[:arg]@frac, e.g. "
            "'rescale:8@0.3,admit@0.5,retire@0.7' — rescale to 8 buckets at "
            "30%% of the duration, admit a worker at 50%%, retire one at 70%%"
        ),
    )
    args = ap.parse_args(argv)
    elastic = []
    for spec in (s.strip() for s in args.elastic_script.split(",")):
        if not spec:
            continue
        head, frac = spec.rsplit("@", 1)
        act, _, arg = head.partition(":")
        elastic.append((act, float(frac), int(arg)) if arg else (act, float(frac)))
    base = args.base_dir or tempfile.mkdtemp(prefix="paimon_cluster_")
    cfg = ClusterConfig(
        workers=args.workers,
        devices_per_worker=args.devices_per_worker,
        buckets=args.buckets,
        duration_s=args.duration,
        seed=args.seed,
        readers=args.readers,
        round_rows=args.round_rows,
        read_amp_ceiling=args.read_amp_ceiling,
        scripted_kills=tuple(s for s in args.scripted_kills.split(",") if s.strip()),
        kill_period_s=args.kill_period,
        sweep_period_s=args.sweep_period,
        compaction=args.compaction,
        elastic=tuple(elastic),
    )
    report = run_cluster_soak(base, cfg)
    print(json.dumps(report, indent=2, default=str))
    ok = report["consistent"] and report["procs_killed"] >= args.min_kills
    if report["procs_killed"] < args.min_kills:
        print(
            f"FAIL: only {report['procs_killed']} kills survived (expected >= {args.min_kills})",
            file=sys.stderr,
        )
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
