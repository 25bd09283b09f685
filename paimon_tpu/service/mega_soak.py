"""Production mega-soak: every plane on one table set, one chaos store,
one oracle, one verdict.

Each plane has its own soak (verify.sh: soak / proc-soak / subscribe /
cluster / get / gateway) — but they never run TOGETHER, so cross-plane
interactions (snapshot expiry racing a subscriber pin, a compaction drain
holding debt charges through a worker respawn, a gateway put conflicting
with a coordinator commit) go untested. This supervisor stands up the full
stack against ONE warehouse on the composed chaos store (faults over
latency over local disk, fs/testing.py) and ends with ONE verdict:

  mega supervisor (this process)
  ├── ClusterCoordinator (in-process: commits, reassignment, adaptive
  │     compaction drain)                                 [cluster cells]
  ├── Gateway + GatewayServer (TCP front door: ≥3 tenants, hedged reads,
  │     route failover, journaled puts)
  ├── cluster worker OS procs   — mesh ingest + serving    [cluster cells]
  ├── direct writer OS procs    — proc_soak protocol       [kv cells]
  ├── gateway writer OS procs   — intent/ack journal, puts THROUGH the
  │     gateway wire (commit identity rides the RPC)
  ├── getter OS procs           — get_batch through the gateway, checking
  │     the writer-id value invariant
  ├── SQL client OS procs       — aggregates + JOINs through the gateway
  ├── subscriber OS procs       — one CDC wire format per cell, journaling
  │     parse∘format round-trips
  ├── reader OS procs           — pinned-snapshot scans (proc_soak reader)
  └── churn threads             — snapshot expiry, consumer expiry, orphan
        sweep, tag/branch creation, an in-process gateway subscriber

A seeded kill schedule SIGKILLs every process kind across all registered
crash points (resilience.faults.ALL_CRASH_POINTS — the per-kind spec
queues below cover all nine, four kinds); every death is respawned and
journal-recovered per the PR 9/15 protocol. The scenario matrix axes:
schema shape (bigint k/v, dict-string PK, wide mixed), bucket mode (fixed
+ dynamic), branches/tags, consumer expiry, CDC wire format, and engine
toggles (mesh merge, dict-domain merge, native manifest codec, lane
compression off).

End of each cell, on the HEALED store: one fold_landed_rounds call over
every plane's journals (user_prefix is a tuple — direct, cluster, and
gateway writers fold together in snapshot-id order), verify_table_state
(full compact → scan == fold → total_record_count == unique keys →
threshold-0 sweep → disk set == reachable closure), subscriber journal
fold == pinned scan at each checkpoint, a quiesced SQL bit-identity
battery (gateway SQL twice + local query once, byte-equal), tag/branch
time travel vs the fold-up-to-tag, and consumer-expiry liveness. The run
verdict is the AND over cells, plus per-plane counters and a metric-group
census (io/soak/get/sub/cluster/sql/gateway/compaction/dict must all be
nonzero somewhere in the matrix).

Run directly:  python -m paimon_tpu.service.mega_soak [base_dir] [flags]
Child roles:   python -m paimon_tpu.service.mega_soak gateway-writer|getter|sql-client ...
"""

from __future__ import annotations

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
from .proc_soak import WriterJournal
from .soak import KEYSPACE, SCHEMA

__all__ = [
    "MegaScenario",
    "MegaConfig",
    "GatewayServer",
    "MegaSoakSupervisor",
    "run_mega_soak",
    "DEFAULT_MATRIX",
    "DEFAULT_MEGA_KILLS",
    "GW_USER_PREFIX",
    "MEGA_USER_PREFIXES",
]

GW_USER_PREFIX = "mega-gw"
# gateway writer w owns keys [(GW_KEY_BASE + w) * KEYSPACE, ...) — disjoint
# from direct writers (wid * KEYSPACE) and cluster workers (small ints), so
# the getter's structural invariant (value encodes the writer id) holds
GW_KEY_BASE = 500
# every plane journals under one of these commit-user prefixes; the fold is
# ONE fold_landed_rounds call over all of them (str.startswith on a tuple)
MEGA_USER_PREFIXES = ("psoak-w", "cluster-w", GW_USER_PREFIX)

# (process kind, crash spec) pairs: popped per kind at spawn while they
# last, then the seeded random SIGKILL timer takes over. Together the specs
# arm every name in resilience.faults.ALL_CRASH_POINTS (the coverage audit
# test asserts this) across four distinct process kinds.
DEFAULT_MEGA_KILLS = (
    ("writer", "commit:manifests-written:2:kill"),
    ("worker", "cluster:before-ship:2:kill"),
    ("gateway-writer", "gateway:put-sent:2:kill"),
    ("subscriber", "subscriber:batch-journaled:2:kill"),
    ("writer", "commit:snapshot-committed:2:kill"),
    ("worker", "cluster:compact-executing:1:kill"),
    ("writer", "flush:files-written:3:kill"),
    ("writer", "commit:before-manifests:2:kill"),
    ("writer", "flush:before-dispatch:2:kill"),
    # the elastic-topology axis (ISSUE 19): workers dying inside the rescale
    # rewrite window and a retiring worker dying after draining but before
    # its retire RPC — armed on the cluster cells, fired by the elastic
    # churn thread's scripted rescale/admit/retire events
    ("worker", "rescale:files-written:1:kill"),
    ("worker", "rescale:before-ship:1:kill"),
    ("worker", "handoff:before-retire:1:kill"),
)

# metric groups the matrix must tick (the acceptance census)
METRIC_GROUPS = (
    "io", "soak", "get", "sub", "cluster", "sql",
    "gateway", "compaction", "dict",
)


# ---------------------------------------------------------------------------
# scenario matrix
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class MegaScenario:
    """One cell of the matrix: a schema shape x bucket mode x CDC wire
    format x engine-toggle combination, with its own process census.
    `table_options` is a tuple of (key, value) pairs (frozen dataclass)."""

    name: str
    schema: str = "kv"  # kv | dict | wide

    @property
    def table_ident(self) -> str:
        """SQL-safe catalog identifier (cell names use hyphens)."""
        return f"mega.{self.name.replace('-', '_')}"

    bucket: int = 4  # -1 = dynamic bucket mode
    cdc_format: str = "debezium-json"
    cluster: bool = False
    direct_writers: int = 1  # proc_soak protocol writers (kv schema only)
    gateway_writers: int = 2
    getters: int = 1
    readers: int = 1
    sql_clients: int = 1
    subscribers: int = 1
    branch_tag: bool = False
    consumer_expiry: bool = False
    table_options: tuple = ()


DEFAULT_MATRIX = (
    # the flagship: every plane at once — cluster mesh ingest + adaptive
    # compaction + direct writers + gateway puts + hedged routed gets +
    # distributed SQL + CDC subscriber + tags/branches
    MegaScenario(
        name="flagship",
        schema="kv",
        bucket=4,
        cdc_format="debezium-json",
        cluster=True,
        direct_writers=1,
        gateway_writers=2,
        branch_tag=True,
    ),
    # dict-string primary key on DYNAMIC buckets, dict-domain merge forced,
    # canal wire format, consumer expiry churn against live heartbeats
    MegaScenario(
        name="dict-dynamic",
        schema="dict",
        bucket=-1,
        cdc_format="canal-json",
        direct_writers=0,
        gateway_writers=2,
        consumer_expiry=True,
        table_options=(("merge.dict-domain", "true"),),
    ),
    # wide mixed schema (float + dict-string + int columns), avro
    # manifests, maxwell wire format
    MegaScenario(
        name="wide",
        schema="wide",
        bucket=2,
        cdc_format="maxwell-json",
        direct_writers=0,
        gateway_writers=2,
        table_options=(("manifest.format", "avro"),),
    ),
    # engine-toggle contrast: numpy sort, lane compression off, plain json
    # wire format, cluster plane on a second kv table
    MegaScenario(
        name="native-legacy",
        schema="kv",
        bucket=4,
        cdc_format="json",
        cluster=True,
        direct_writers=1,
        gateway_writers=1,
        table_options=(("sort-engine", "numpy"), ("merge.lane-compression", "false")),
    ),
)


@dataclass
class MegaConfig:
    duration_s: float = 45.0  # per cell
    cluster_workers: int = 2
    seed: int = 0
    scenarios: tuple = DEFAULT_MATRIX
    scripted_kills: tuple = DEFAULT_MEGA_KILLS
    kill_period_s: float = 9.0  # mean seconds between random SIGKILLs (0 = scripted only)
    sweep_period_s: float = 14.0
    sweep_older_than_ms: int = 45_000
    expire_period_s: float = 6.0
    consumer_expire_ms: int = 8_000
    # the composed chaos store: latency shaping + probabilistic faults
    chaos_read_ms: float = 1.0
    chaos_write_ms: float = 0.5
    chaos_possibility: int = 200  # one op in N raises ArtificialException
    chaos_max_fails: int = 1 << 30
    rows_per_commit: int = 200  # direct writers
    gw_rows_per_commit: int = 120  # gateway writers
    round_rows: int = 96  # cluster workers, per owned bucket per round
    table_options: dict = field(default_factory=dict)

    @classmethod
    def from_table_options(cls, options) -> "MegaConfig":
        from ..options import CoreOptions

        o = options.options
        return cls(
            duration_s=o.get(CoreOptions.SOAK_MEGA_DURATION) / 1000.0,
            cluster_workers=o.get(CoreOptions.SOAK_MEGA_CLUSTER_WORKERS),
            kill_period_s=o.get(CoreOptions.SOAK_MEGA_KILL_PERIOD) / 1000.0,
            chaos_read_ms=float(o.get(CoreOptions.SOAK_MEGA_CHAOS_READ)),
            chaos_write_ms=float(o.get(CoreOptions.SOAK_MEGA_CHAOS_WRITE)),
            chaos_possibility=o.get(CoreOptions.SOAK_MEGA_CHAOS_POSSIBILITY),
        )


def scenario_schema(kind: str):
    """The RowType for a matrix schema shape. Key column is always 'k'."""
    from ..types import BIGINT, DOUBLE, STRING, RowType

    if kind == "kv":
        return SCHEMA
    if kind == "dict":
        return RowType.of(("k", STRING()), ("v", STRING()))
    if kind == "wide":
        return RowType.of(
            ("k", BIGINT()), ("v", DOUBLE()), ("tag", STRING()), ("aux", BIGINT())
        )
    raise ValueError(f"unknown mega schema {kind!r}")


# ---------------------------------------------------------------------------
# gateway TCP front door (the wire the client processes speak)
# ---------------------------------------------------------------------------
class GatewayServer:
    """The Gateway as a network service: length-prefixed JSON over TCP (the
    KvQueryServer protocol), methods put / get_batch / sql / slo / ping.
    Typed sheds serialize as {"shed": ShedInfo payload} — the client can
    tell pressure from failure without exception classes on the wire."""

    def __init__(self, gateway, host: str = "127.0.0.1", port: int = 0):
        from .gateway import GatewayShedError

        self.gateway = gateway
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
                        out.setdefault("ok", True)
                    except GatewayShedError as e:
                        out = {"ok": False, "shed": e.shed_info.to_payload()}
                    except Exception as e:  # noqa: BLE001 — surface to the client
                        out = {
                            "ok": False,
                            "error": f"{type(e).__name__}: {e}",
                            "etype": type(e).__name__,
                        }
                    out["id"] = rid
                    try:
                        _send(self.request, out)
                    except OSError:
                        return

        self._server = socketserver.ThreadingTCPServer((host, port), Handler)
        self._server.daemon_threads = True
        self.host, self.port = self._server.server_address[:2]
        self._thread: threading.Thread | None = None

    def start(self) -> "GatewayServer":
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="mega-gw-server", daemon=True
        )
        self._thread.start()
        return self

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()

    def _dispatch(self, method: str, req: dict) -> dict:
        gw = self.gateway
        if method == "ping":
            return {}
        if method == "put":
            sid = gw.put(
                req["rows"],
                kinds=req.get("kinds"),
                tenant=req.get("tenant"),
                user=req.get("user"),
                identifier=req.get("identifier"),
            )
            return {"sid": sid}
        if method == "get_batch":
            keys = [tuple(k) if isinstance(k, list) else k for k in req["keys"]]
            rows = gw.get_batch(keys, tenant=req.get("tenant"))
            return {"rows": [None if r is None else list(r) for r in rows]}
        if method == "sql":
            out = gw.sql(req["stmt"], tenant=req.get("tenant"))
            return {"cols": list(out.schema.field_names), "rows": out.to_pylist()}
        if method == "slo":
            return {"slo": gw.slo()}
        raise ValueError(f"unknown method {method!r}")


class GatewayClient:
    """One dedicated connection to the GatewayServer. `call` returns the
    raw response dict ({"ok": ...} / {"shed": ...} / {"error": ...});
    `retry=True` reconnects once on a connection-grain failure — safe ONLY
    for idempotent reads, never for put (the journal protocol resolves a
    lost put response from the snapshot chain instead)."""

    def __init__(self, host: str, port: int, timeout_s: float = 120.0):
        self.addr = (host, int(port))
        self.timeout_s = timeout_s
        self._sock: socket.socket | None = None
        self._seq = 0

    def call(self, method: str, retry: bool = True, **kw) -> dict:
        for attempt in (0, 1):
            try:
                if self._sock is None:
                    self._sock = socket.create_connection(self.addr, timeout=self.timeout_s)
                self._seq += 1
                _send(self._sock, {"id": self._seq, "method": method, **kw})
                r = _recv(self._sock)
                if r is None:
                    raise ConnectionError("gateway closed the connection")
                return r
            except (OSError, ConnectionError):
                self.close()
                if not retry or attempt:
                    raise
        raise AssertionError("unreachable")

    def close(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None


# ---------------------------------------------------------------------------
# child process: gateway writer (journaled puts THROUGH the front door)
# ---------------------------------------------------------------------------
def _gw_fresh_keys(schema: str, wid: int, start: int, n: int) -> list:
    if schema == "dict":
        return [f"gw{wid}-{start + i:08d}" for i in range(n)]
    return [(GW_KEY_BASE + wid) * KEYSPACE + start + i for i in range(n)]


def _gw_value(schema: str, wid: int, ident: int, rng) -> object:
    """Value encodings carry the writer id structurally, so a getter can
    check rows it has no journal for: kv/wide floor(v) % 1000 == wid; dict
    value 'wid:ident:salt' prefixed with the wid."""
    if schema == "dict":
        return f"{wid}:{ident}:{int(rng.integers(0, 1 << 30))}"
    v = float(ident * 1_000.0 + wid + rng.random())
    if schema == "wide":
        return [v, f"t{ident % 5}", int(ident)]
    return v


def _gw_wire_columns(schema: str, rows: dict) -> dict:
    ks = list(rows)
    if schema == "wide":
        vals = [rows[k] for k in ks]
        return {
            "k": ks,
            "v": [r[0] for r in vals],
            "tag": [r[1] for r in vals],
            "aux": [r[2] for r in vals],
        }
    return {"k": ks, "v": [rows[k] for k in ks]}


def gateway_writer_main(args) -> int:
    """Exactly the proc_soak writer protocol — intent fsynced before the
    round, ack after — except the commit happens on the far side of a wire:
    Gateway.put carries (user, identifier) so the snapshot still records
    this writer's identity, and a lost response (connection death, or the
    armed gateway:put-sent crash between the response and the ack) resolves
    from the chain via find_landed_append, adopt-never-replay."""
    from ..resilience.faults import crash_point
    from ..table import load_table
    from .oracle import find_landed_append

    if args.table.startswith(("fail:", "fail-s3", "latency:", "traceable:", "chaos:")):
        from ..fs import testing as _testing  # noqa: F401

    wid = args.wid
    user = f"{GW_USER_PREFIX}{wid}"
    rng = np.random.default_rng(args.seed * 6151 + wid * 104729 + args.incarnation)
    events = WriterJournal.read(args.journal)
    intents = [e for e in events if e["t"] == "intent"]
    resolved = {e["ident"] for e in events if e["t"] in ("ack", "recovered", "abort")}
    acked = {e["ident"] for e in events if e["t"] in ("ack", "recovered")}
    next_ident = max((e["ident"] for e in intents), default=0) + 1
    next_key = max((e["fresh"][0] + e["fresh"][1] for e in intents), default=0)
    decode = str if args.schema == "dict" else int
    landed_keys = [decode(k) for e in intents if e["ident"] in acked for k in e["rows"]]

    # probe-only handle: recovery reads the snapshot chain directly — the
    # gateway may itself be restarting when this incarnation comes up
    table = load_table(args.table, commit_user=user)
    store = table.store
    journal = WriterJournal(args.journal).open()
    recovered = 0
    for e in intents:
        if e["ident"] in resolved:
            continue
        sid = find_landed_append(store, user, e["ident"])
        if sid is not None:
            journal.recovered(e["ident"], sid)
            landed_keys.extend(decode(k) for k in e["rows"])
            recovered += 1
        else:
            journal.abort(e["ident"])
    if recovered:
        print(
            f"gateway writer {wid} incarnation {args.incarnation}: "
            f"recovered {recovered} landed-unacked round(s)",
            flush=True,
        )

    host, port = args.gateway.rsplit(":", 1)
    # a put wedged behind the gateway's put lock (commit-conflict retries
    # under chaos latency) must surface within the drain budget: a timeout
    # is just a lost response, and the chain probe resolves it safely
    client = GatewayClient(host, int(port), timeout_s=30.0)
    rounds = 0
    while rounds < args.max_rounds and not os.path.exists(args.stop_file):
        ident = next_ident
        next_ident += 1
        rounds += 1
        n_upd = int(args.rows_per_commit * args.update_fraction) if landed_keys else 0
        n_new = args.rows_per_commit - n_upd
        fresh = _gw_fresh_keys(args.schema, wid, next_key, n_new)
        upd = (
            [landed_keys[i] for i in rng.integers(0, len(landed_keys), n_upd)]
            if n_upd
            else []
        )
        rows = {k: _gw_value(args.schema, wid, ident, rng) for k in fresh + upd}
        journal.intent(ident, next_key, n_new, rows)
        next_key += n_new
        try:
            r = client.call(
                "put",
                retry=False,  # a lost put resolves via the chain, never a resend
                rows=_gw_wire_columns(args.schema, rows),
                tenant=args.tenant,
                user=user,
                identifier=ident,
            )
        except (ConnectionError, OSError):
            sid = find_landed_append(store, user, ident)
            if sid is not None:
                journal.ack(ident, sid)
                landed_keys.extend(fresh)
            else:
                journal.abort(ident)
            time.sleep(0.2)
            continue
        # the wire exchange completed: this is the landed-but-unacked edge
        # the mega kill schedule arms (gateway:put-sent) — death here leaves
        # the round for the NEXT incarnation's chain probe
        crash_point("gateway:put-sent")
        if r.get("ok"):
            sid = r.get("sid")
            if sid is not None:
                journal.ack(ident, sid)
                landed_keys.extend(fresh)
            else:
                journal.abort(ident)  # nothing committed (empty round)
        elif "shed" in r:
            # typed pressure: verifiably rejected before any byte buffered
            journal.abort(ident)
            time.sleep(max(float(r["shed"].get("retry_after_ms", 25)), 1.0) / 1000.0)
        else:
            # an error crossed the wire (commit conflict give-up, injected
            # fault escaping the retry budget): the chain is the truth
            sid = find_landed_append(store, user, ident)
            if sid is not None:
                journal.ack(ident, sid)
                landed_keys.extend(fresh)
            else:
                journal.abort(ident)
    journal.close()
    client.close()
    return 0


# ---------------------------------------------------------------------------
# child process: getter (point reads through the gateway)
# ---------------------------------------------------------------------------
def _check_row(schema: str, wid: int, row: list) -> bool:
    """Does a returned full row carry writer `wid`'s value encoding?"""
    try:
        if schema == "dict":
            return str(row[1]).split(":", 1)[0] == str(wid)
        return int(float(row[1])) % 1000 == wid
    except (IndexError, TypeError, ValueError):
        return False


def getter_main(args) -> int:
    """Point-gets through the gateway against gateway-writer key ranges,
    asserting the structural value invariant on every non-None row. Typed
    sheds back off; mismatches and unclassified failures are read errors
    (the JSONL log folds through oracle.read_client_logs)."""
    host, port = args.gateway.rsplit(":", 1)
    client = GatewayClient(host, int(port), timeout_s=20.0)
    rng = np.random.default_rng(args.seed * 31 + args.gid * 977 + 5)
    ok = errors = 0
    with open(args.log, "a", buffering=1) as log:
        while not os.path.exists(args.stop_file):
            w = int(rng.integers(0, max(args.gw_writers, 1)))
            offs = rng.integers(0, args.window, 16)
            if args.schema == "dict":
                keys = [f"gw{w}-{int(n):08d}" for n in offs]
            else:
                keys = [int((GW_KEY_BASE + w) * KEYSPACE + n) for n in offs]
            try:
                r = client.call("get_batch", keys=keys, tenant=args.tenant)
            except (ConnectionError, OSError) as exc:
                errors += 1
                log.write(json.dumps({"t": "err", "exc": repr(exc)}) + "\n")
                time.sleep(0.3)
                continue
            if r.get("ok"):
                bad = [
                    row
                    for row in r["rows"]
                    if row is not None and not _check_row(args.schema, w, row)
                ]
                if bad:
                    errors += 1
                    log.write(
                        json.dumps({"t": "err", "kind": "wid-mismatch", "wid": w, "sample": bad[:2]})
                        + "\n"
                    )
                else:
                    ok += 1
            elif "shed" in r:
                time.sleep(max(float(r["shed"].get("retry_after_ms", 25)), 1.0) / 1000.0)
            else:
                errors += 1
                log.write(json.dumps({"t": "err", "exc": r.get("error")}) + "\n")
                time.sleep(0.2)
            time.sleep(0.04)
        log.write(json.dumps({"t": "done", "reads_ok": ok, "read_errors": errors}) + "\n")
    client.close()
    return 0


# ---------------------------------------------------------------------------
# child process: SQL client (aggregates + joins through the gateway)
# ---------------------------------------------------------------------------
def _sql_statements(schema: str, table_ident: str, cluster: bool) -> list[str]:
    stmts = [f"SELECT count(*) FROM {table_ident}"]
    if schema in ("kv", "wide"):
        stmts.append(f"SELECT count(*), sum(v), min(v), max(v) FROM {table_ident}")
    if schema == "wide":
        stmts.append(f"SELECT tag, count(*), sum(aux) FROM {table_ident} GROUP BY tag")
    if cluster and schema == "kv":
        # cluster-worker keys are small ints: the dim table covers them, so
        # the distributed join path returns real matches mid-soak
        stmts.append(
            f"SELECT d.name, count(*) FROM {table_ident} f "
            f"JOIN mega.dim d ON f.k = d.k GROUP BY d.name"
        )
    return stmts


def sql_client_main(args) -> int:
    """Aggregates (and, on cluster cells, distributed JOINs) through the
    gateway's SQL plane while every other plane churns. One in-flight retry
    per statement — a worker respawn surfaces as a typed route shed with a
    backoff, never a client failure."""
    host, port = args.gateway.rsplit(":", 1)
    client = GatewayClient(host, int(port), timeout_s=30.0)
    rng = np.random.default_rng(args.seed * 131 + args.cid * 7 + 11)
    stmts = _sql_statements(args.schema, args.ident, args.cluster)
    ok = errors = 0
    with open(args.log, "a", buffering=1) as log:
        while not os.path.exists(args.stop_file):
            stmt = stmts[int(rng.integers(0, len(stmts)))]
            failed = None
            for _ in range(3):
                try:
                    r = client.call("sql", stmt=stmt, tenant=args.tenant)
                except (ConnectionError, OSError) as exc:
                    failed = repr(exc)
                    time.sleep(0.3)
                    continue
                if r.get("ok"):
                    failed = None
                    ok += 1
                    break
                if "shed" in r:
                    failed = "shed"
                    time.sleep(
                        max(float(r["shed"].get("retry_after_ms", 25)), 1.0) / 1000.0
                    )
                    continue
                failed = r.get("error")
                time.sleep(0.2)
            if failed is not None and failed != "shed":
                errors += 1
                log.write(json.dumps({"t": "err", "stmt": stmt, "exc": failed}) + "\n")
            time.sleep(0.15)
        log.write(json.dumps({"t": "done", "reads_ok": ok, "read_errors": errors}) + "\n")
    client.close()
    return 0


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------
class MegaSoakSupervisor:
    """One warehouse, one chaos store, every plane, one verdict."""

    def __init__(self, base_dir: str, cfg: "MegaConfig | None" = None):
        from ..fs.testing import CHAOS_ENV, chaos_spec  # registers chaos://

        self.cfg = cfg or MegaConfig()
        self.base_dir = str(base_dir)
        self.warehouse_posix = os.path.join(self.base_dir, "warehouse")
        self.run_root = os.path.join(self.base_dir, "mega_run")
        self.domain = f"mega{self.cfg.seed}"
        self.chaos_env_key = CHAOS_ENV
        self.chaos_value = chaos_spec(
            self.domain,
            read_ms=self.cfg.chaos_read_ms,
            write_ms=self.cfg.chaos_write_ms,
            possibility=self.cfg.chaos_possibility,
            max_fails=self.cfg.chaos_max_fails,
            seed=self.cfg.seed,
        )
        self.warehouse = f"chaos://{self.domain}{os.path.abspath(self.warehouse_posix)}"
        self.cells: list[dict] = []
        self.counts = {
            "procs_spawned": 0,
            "procs_killed": 0,
            "procs_respawned": 0,
            "child_errors": 0,
            "sweeps_during_soak": 0,
            "snapshot_expiries": 0,
            "faults_injected": 0,
            "rescales_requested": 0,
            "workers_admitted": 0,
            "workers_retired": 0,
        }
        self.kills_by_kind: dict[str, int] = {}
        self.kills_by_point: dict[str, int] = {}

    # ---- chaos lifecycle ----------------------------------------------
    def _arm_chaos(self) -> None:
        from ..fs.testing import apply_chaos_env

        os.environ[self.chaos_env_key] = self.chaos_value
        apply_chaos_env(self.chaos_value)

    def _heal_chaos(self) -> None:
        """Verification runs on the healed store: drop latency shaping and
        the fault domain (chaos:// then degrades to plain local IO), after
        banking the injected-fault count."""
        from ..fs.testing import FailingFileIO, LatencyFileIO

        self.counts["faults_injected"] += FailingFileIO.fails_injected(self.domain)
        os.environ.pop(self.chaos_env_key, None)
        FailingFileIO._states.pop(self.domain, None)
        LatencyFileIO.configure(read_ms=0.0, write_ms=0.0)

    # ---- table/catalog setup ------------------------------------------
    def _catalog(self):
        from ..catalog import FileSystemCatalog

        return FileSystemCatalog(self.warehouse, commit_user="mega-supervisor")

    def _cell_table_options(self, sc: MegaScenario) -> dict:
        cfg = self.cfg
        opts = {
            "bucket": str(sc.bucket),
            "write-buffer-rows": "256",
            # the resilience budget that turns chaos faults into retries —
            # without it an ArtificialException (an IOError) would escape a
            # gateway put as an UNTYPED shed and fail the acceptance gate
            "commit.max-retries": "30",
            "commit.retry-backoff": "2 ms",
            "fs.retry.max-attempts": "6",
            "fs.retry.initial-backoff": "2 ms",
            "fs.retry.max-backoff": "40 ms",
            "snapshot.num-retained.min": "16",
            "snapshot.num-retained.max": "30",
            "subscription.queue-depth": "4",
            "subscription.heartbeat-interval": "1 s",
            "subscription.poll-backoff": "20 ms",
            # three tenants with distinct weights: ingest > serve > analytics
            "gateway.tenant.ingest.weight": "3.0",
            "gateway.tenant.ingest.max-inflight": "8",
            "gateway.tenant.serve.weight": "2.0",
            "gateway.tenant.serve.max-inflight": "8",
            "gateway.tenant.analytics.weight": "1.0",
            "gateway.tenant.analytics.max-inflight": "4",
            "gateway.hedge.enabled": "true",
            "gateway.hedge.deadline-ms": "60",
            "gateway.hedge.max-fraction": "0.5",
        }
        if sc.cluster:
            opts.update(
                {
                    "write-only": "true",  # compaction belongs to the coordinator drain
                    "merge.engine": "mesh",
                    "cluster.workers": str(cfg.cluster_workers),
                    "compaction.adaptive.read-amp-ceiling": "12",
                    "compaction.adaptive.interval": "300 ms",
                    "compaction.adaptive.max-buckets-per-round": "2",
                }
            )
        opts.update(dict(sc.table_options))
        opts.update(cfg.table_options)
        return opts

    def _ensure_dim_table(self, catalog) -> None:
        """The static join dimension (k BIGINT, name STRING): keys 0..4095
        cover the cluster workers' small-int key pools, so mid-soak
        distributed JOINs return real matches."""
        from ..core.manifest import ManifestCommittable
        from ..data.batch import ColumnBatch
        from ..table.write import TableWrite
        from ..types import BIGINT, STRING, RowType

        dim_type = RowType.of(("k", BIGINT()), ("name", STRING()))
        table = catalog.create_table(
            "mega.dim",
            dim_type,
            primary_keys=["k"],
            options={"bucket": "2", "fs.retry.max-attempts": "6"},
            ignore_if_exists=True,
        )
        if table.store.snapshot_manager.latest_snapshot_id() is not None:
            return
        ks = list(range(4096))
        tw = TableWrite(table)
        try:
            tw.write(
                ColumnBatch.from_pydict(
                    dim_type, {"k": ks, "name": [f"n{k % 7}" for k in ks]}
                )
            )
            msgs = tw.prepare_commit()
        finally:
            tw.close()
        table.store.new_commit().commit(ManifestCommittable(1, messages=msgs))

    # ---- child process plumbing ---------------------------------------
    def _child_env(self, crash_spec: "str | None", role: "str | None" = None,
                   devices: int = 0) -> dict:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("PAIMON_TPU_CRASH_POINT", None)
        env.pop("PAIMON_TPU_CLUSTER_ROLE", None)
        if crash_spec:
            env["PAIMON_TPU_CRASH_POINT"] = crash_spec
        if role:
            env["PAIMON_TPU_CLUSTER_ROLE"] = role
        if devices:
            flags = " ".join(
                f
                for f in env.get("XLA_FLAGS", "").split()
                if not f.startswith("--xla_force_host_platform_device_count")
            )
            env["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={devices}"
            ).strip()
        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        env["PYTHONPATH"] = repo_root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
        return env

    def _next_spec(self, kind: str) -> "str | None":
        queue = self._spec_queues.get(kind)
        return queue.pop(0) if queue else None

    def _spawn(self, cell, kind: str, idx: int, cmd: list, *, crash_armed: bool,
               role: "str | None" = None, devices: int = 0) -> None:
        from ..metrics import soak_metrics

        spec = self._next_spec(kind) if crash_armed else None
        inc = self._incarnations.get((kind, idx), 0)
        self._incarnations[(kind, idx)] = inc + 1
        log = open(os.path.join(cell["run_dir"], f"{kind}-{idx}.{inc}.log"), "wb")
        p = subprocess.Popen(
            cmd,
            stdout=log,
            stderr=subprocess.STDOUT,
            env=self._child_env(spec, role=role, devices=devices),
        )
        log.close()
        self._procs[(kind, idx)] = (p, spec)
        self.counts["procs_spawned"] += 1
        soak_metrics().counter("procs_spawned").inc()

    def _spawn_child(self, cell, kind: str, idx: int) -> None:
        """(Re)spawn one child of `kind` for this cell — the factory the
        kill/respawn loop calls, so every respawn re-arms from the same
        per-kind crash-spec queues."""
        sc: MegaScenario = cell["scenario"]
        cfg = self.cfg
        run_dir = cell["run_dir"]
        table_uri = cell["table_uri"]
        if kind == "writer":
            cmd = [
                sys.executable, "-m", "paimon_tpu.service.proc_soak", "writer",
                "--table", table_uri,
                "--wid", str(idx),
                "--journal", os.path.join(run_dir, f"direct-journal-{idx}.jsonl"),
                "--stop-file", cell["stop_file"],
                "--seed", str(cfg.seed),
                "--incarnation", str(self._incarnations.get((kind, idx), 0)),
                "--rows-per-commit", str(cfg.rows_per_commit),
                "--chunk-rows", "100",
                "--update-fraction", "0.3",
                # a write-only cluster table refuses writer-side compaction
                "--compact-every", "0" if sc.cluster else "5",
                "--max-memory", str(256 * 1024),
                "--block-timeout-ms", "20000",
            ]
            self._spawn(cell, kind, idx, cmd, crash_armed=True)
        elif kind == "worker":
            cmd = [
                sys.executable, "-m", "paimon_tpu.service.cluster", "worker",
                "--table", table_uri,
                "--wid", str(idx),
                "--coordinator", f"{cell['coordinator'].host}:{cell['coordinator'].port}",
                "--journal", os.path.join(run_dir, f"cluster-journal-{idx}.jsonl"),
                "--incarnation", str(self._incarnations.get((kind, idx), 0)),
                "--seed", str(cfg.seed),
                "--round-rows", str(cfg.round_rows),
                "--devices", "2",
                "--admit-timeout", "30.0",
                "--heartbeat-interval", "0.5",
            ]
            self._spawn(cell, kind, idx, cmd, crash_armed=True, role="worker", devices=2)
        elif kind == "gateway-writer":
            cmd = [
                sys.executable, "-m", "paimon_tpu.service.mega_soak", "gateway-writer",
                "--table", table_uri,
                "--gateway", f"{cell['server'].host}:{cell['server'].port}",
                "--wid", str(idx),
                "--schema", sc.schema,
                "--journal", os.path.join(run_dir, f"gw-journal-{idx}.jsonl"),
                "--stop-file", cell["stop_file"],
                "--seed", str(cfg.seed),
                "--incarnation", str(self._incarnations.get((kind, idx), 0)),
                "--rows-per-commit", str(cfg.gw_rows_per_commit),
                "--tenant", "ingest",
            ]
            self._spawn(cell, kind, idx, cmd, crash_armed=True)
        elif kind == "subscriber":
            remaining = max(cell["deadline"] - time.monotonic(), 1.0)
            cmd = [
                sys.executable, "-m", "paimon_tpu.service.subscription",
                "--table", table_uri,
                "--consumer", f"mega-sub-{idx}",
                "--journal", os.path.join(run_dir, f"sub-{idx}.jsonl"),
                "--duration", str(remaining + 5.0),
                "--from-snapshot", "1",
                "--format", sc.cdc_format,
            ]
            self._spawn(cell, kind, idx, cmd, crash_armed=True)
        elif kind == "getter":
            cmd = [
                sys.executable, "-m", "paimon_tpu.service.mega_soak", "getter",
                "--gateway", f"{cell['server'].host}:{cell['server'].port}",
                "--gid", str(idx),
                "--schema", sc.schema,
                "--gw-writers", str(sc.gateway_writers),
                "--log", os.path.join(run_dir, f"gets-{idx}.jsonl"),
                "--stop-file", cell["stop_file"],
                "--seed", str(cfg.seed),
                "--tenant", "serve",
            ]
            self._spawn(cell, kind, idx, cmd, crash_armed=False)
        elif kind == "sql-client":
            cmd = [
                sys.executable, "-m", "paimon_tpu.service.mega_soak", "sql-client",
                "--gateway", f"{cell['server'].host}:{cell['server'].port}",
                "--cid", str(idx),
                "--schema", sc.schema,
                "--ident", sc.table_ident,
                "--log", os.path.join(run_dir, f"sql-{idx}.jsonl"),
                "--stop-file", cell["stop_file"],
                "--seed", str(cfg.seed),
                "--tenant", "analytics",
            ] + (["--cluster"] if sc.cluster else [])
            self._spawn(cell, kind, idx, cmd, crash_armed=False)
        elif kind == "reader":
            cmd = [
                sys.executable, "-m", "paimon_tpu.service.proc_soak", "reader",
                "--table", table_uri,
                "--rid", str(idx),
                "--log", os.path.join(run_dir, f"reads-{idx}.jsonl"),
                "--stop-file", cell["stop_file"],
            ]
            self._spawn(cell, kind, idx, cmd, crash_armed=False)
        else:
            raise ValueError(f"unknown child kind {kind!r}")

    def _reap(self, cell, kind: str, idx: int, rc: int, spec: "str | None") -> None:
        from ..metrics import soak_metrics
        from ..resilience.faults import KILL_EXIT_CODE, _parse_spec

        if rc == KILL_EXIT_CODE or rc < 0:
            self.counts["procs_killed"] += 1
            self.kills_by_kind[kind] = self.kills_by_kind.get(kind, 0) + 1
            # rc == 137 is os._exit at an ARMED point; rc < 0 is the seeded
            # random SIGKILL (Popen reports the signal as a negative rc)
            point = _parse_spec(spec)[0] if (spec and rc == KILL_EXIT_CODE) else "random-sigkill"
            self.kills_by_point[point] = self.kills_by_point.get(point, 0) + 1
            soak_metrics().counter("procs_killed").inc()
        elif rc != 0:
            self.counts["child_errors"] += 1
            inc = self._incarnations.get((kind, idx), 1) - 1
            log = os.path.join(cell["run_dir"], f"{kind}-{idx}.{inc}.log")
            tail = ""
            if os.path.exists(log):
                with open(log, "rb") as f:
                    tail = f.read()[-2000:].decode(errors="replace")
            cell["errors"].append(f"{kind} {idx} exited rc={rc}:\n{tail}")

    # ---- churn threads -------------------------------------------------
    def _churn_loop(self, cell, deadline: float) -> None:
        """Snapshot expiry + orphan sweep + consumer expiry + tag/branch
        creation, all racing the write/read/subscribe planes."""
        from ..resilience.orphan import remove_orphan_files
        from ..table import load_table
        from ..table.consumer import ConsumerManager

        sc: MegaScenario = cell["scenario"]
        cfg = self.cfg
        table = load_table(cell["table_uri"], commit_user="mega-churn")

        _FAILED = object()  # hard failure (recorded) vs None = IO fault, retry

        def churn_try(label: str, fn):
            """Background churn under live chaos: an IOError here IS the
            fault injector working (the next period retries); anything else
            is a real defect and fails the cell."""
            try:
                return fn()
            except IOError:
                cell["churn_io_faults"] = cell.get("churn_io_faults", 0) + 1
                return None
            except Exception:
                cell["errors"].append(f"{label} crashed:\n{traceback.format_exc()}")
                return _FAILED

        next_expire = time.monotonic() + cfg.expire_period_s
        next_sweep = time.monotonic() + cfg.sweep_period_s
        next_consumer = time.monotonic() + cfg.expire_period_s
        tag_at = time.monotonic() + 0.4 * cfg.duration_s if sc.branch_tag else float("inf")
        branch_at = time.monotonic() + 0.6 * cfg.duration_s if sc.branch_tag else float("inf")
        if sc.consumer_expiry:
            # the decoy: a consumer nobody heartbeats, destined to expire
            # while the live subscribers' beats keep theirs fresh
            ConsumerManager(table.file_io, table.path).record("mega-dead", 1)
        while time.monotonic() < deadline and not cell["stop"].is_set():
            now = time.monotonic()
            if now >= next_expire:
                r = churn_try("snapshot expiry", table.expire_snapshots)
                if r is not None and r is not _FAILED:
                    self.counts["snapshot_expiries"] += 1
                next_expire = now + cfg.expire_period_s
            if now >= next_sweep:
                r = churn_try(
                    "mid-soak sweep",
                    lambda: remove_orphan_files(
                        table, older_than_millis=cfg.sweep_older_than_ms
                    ),
                )
                if r is not None and r is not _FAILED:
                    self.counts["sweeps_during_soak"] += 1
                next_sweep = now + cfg.sweep_period_s
            if sc.consumer_expiry and now >= next_consumer:
                expired = churn_try(
                    "consumer expiry",
                    lambda: ConsumerManager(table.file_io, table.path).expire_stale(
                        cfg.consumer_expire_ms
                    ),
                )
                if expired is not None and expired is not _FAILED:
                    cell["expired_consumers"].update(expired)
                next_consumer = now + cfg.expire_period_s
            if now >= tag_at:
                from ..sql import call as sql_call

                done = churn_try(
                    "create_tag",
                    lambda: sql_call(
                        cell["catalog"], f"CALL sys.create_tag('{sc.table_ident}', 'mega-v1')"
                    ),
                )
                if done is not None:  # landed or hard-failed; an IO fault retries
                    tag_at = float("inf")
                if done is not None and done is not _FAILED:
                    cell["tagged"] = True
            if now >= branch_at and cell.get("tagged"):
                from ..sql import call as sql_call

                done = churn_try(
                    "create_branch",
                    lambda: sql_call(
                        cell["catalog"],
                        f"CALL sys.create_branch('{sc.table_ident}', 'exp', 'mega-v1')",
                    ),
                )
                if done is not None:
                    branch_at = float("inf")
                if done is not None and done is not _FAILED:
                    cell["branched"] = True
            time.sleep(0.2)

    def _gw_subscriber_loop(self, cell, deadline: float) -> None:
        """An in-process subscriber THROUGH the gateway: exercises the
        gateway subscribe plane (and the sub{...} metric group) beside the
        journaled subscriber OS processes."""
        from .gateway import GatewayShedError

        gw = cell["gateway"]
        sub_id = None
        rows = 0
        while time.monotonic() < deadline and not cell["stop"].is_set():
            try:
                if sub_id is None:
                    sub_id = gw.subscribe_open(
                        consumer_id="mega-gwsub", from_snapshot=1, tenant="serve"
                    )
                out = gw.subscribe_poll(sub_id, timeout_ms=500, tenant="serve")
                rows += len(out.get("rows", ()))
            except GatewayShedError as e:
                sub_id = None
                time.sleep(max(int(e.shed_info.retry_after_ms or 25), 1) / 1000.0)
            except Exception:
                sub_id = None
                time.sleep(0.3)
        cell["gw_sub_rows"] = rows
        if sub_id is not None:
            try:
                gw.subscribe_close(sub_id)
            except Exception:
                pass

    def _elastic_loop(self, cell, t_start: float, deadline: float) -> None:
        """The elastic-topology axis on cluster cells: one live rescale
        under the full chaos load, one worker admit (the join-steal range
        handoff), one planned retire (drain + handoff) — scripted at fixed
        fractions of the cell duration so the armed rescale:*/handoff:*
        crash specs have a live window to fire in."""
        sc: MegaScenario = cell["scenario"]
        coord = cell["coordinator"]
        dur = max(deadline - t_start, 1.0)
        plan = []
        if sc.bucket > 0:  # dynamic tables assign buckets per key
            plan.append((t_start + 0.35 * dur, "rescale"))
        plan.append((t_start + 0.55 * dur, "admit"))
        plan.append((t_start + 0.75 * dur, "retire"))
        while plan and time.monotonic() < deadline and not cell["stop"].is_set():
            if time.monotonic() < plan[0][0]:
                time.sleep(0.2)
                continue
            _, act = plan.pop(0)
            try:
                if act == "rescale":
                    r = coord.start_rescale(coord.num_buckets * 2)
                    if r.get("started"):
                        self.counts["rescales_requested"] += 1
                elif act == "admit":
                    idx = 1 + max(
                        (i for k, i in self._procs if k == "worker"), default=-1
                    )
                    self._spawn_child(cell, "worker", idx)
                    self.counts["workers_admitted"] += 1
                elif act == "retire":
                    live = sorted(
                        i
                        for (k, i), (p, _) in list(self._procs.items())
                        if k == "worker"
                        and p.poll() is None
                        and ("worker", i) not in cell["no_respawn"]
                    )
                    if len(live) > 1:  # never retire the last worker
                        wid = live[-1]  # the admitted joiner when present
                        cell["no_respawn"].add(("worker", wid))
                        coord.request_retire(wid)
                        self.counts["workers_retired"] += 1
            except Exception:
                cell["errors"].append(f"elastic {act} failed:\n{traceback.format_exc()}")

    # ---- one cell ------------------------------------------------------
    def _census(self, sc: MegaScenario) -> dict[str, int]:
        counts = {
            "writer": sc.direct_writers if sc.schema == "kv" else 0,
            "worker": self.cfg.cluster_workers if sc.cluster else 0,
            "gateway-writer": sc.gateway_writers,
            "subscriber": sc.subscribers,
            "getter": sc.getters,
            "sql-client": sc.sql_clients,
            "reader": sc.readers,
        }
        return {k: v for k, v in counts.items() if v > 0}

    def run_cell(self, sc: MegaScenario) -> dict:
        from ..metrics import gateway_metrics
        from .cluster import ClusterClient, ClusterConfig, ClusterCoordinator
        from .gateway import Gateway

        cfg = self.cfg
        # the untyped-shed gate is a per-cell DELTA of the process-global
        # counter — bank the baseline before any gateway traffic
        untyped_at_start = gateway_metrics().counter("sheds_untyped").count
        run_dir = os.path.join(self.run_root, sc.name)
        os.makedirs(run_dir, exist_ok=True)
        self._arm_chaos()
        catalog = self._catalog()
        schema = scenario_schema(sc.schema)
        catalog.create_table(
            sc.table_ident,
            schema,
            primary_keys=["k"],
            options=self._cell_table_options(sc),
            ignore_if_exists=True,
        )
        if sc.schema == "kv":
            self._ensure_dim_table(catalog)
        table = catalog.get_table(sc.table_ident)
        cell: dict = {
            "scenario": sc,
            "run_dir": run_dir,
            "table_uri": catalog.table_path(sc.table_ident),
            "stop_file": os.path.join(run_dir, "stop"),
            "stop": threading.Event(),
            "catalog": catalog,
            "errors": [],
            "inconsistencies": [],
            "expired_consumers": set(),
            "untyped_at_start": untyped_at_start,
            "no_respawn": set(),  # retired workers stay retired
        }
        self._procs: dict[tuple, tuple] = {}
        self._incarnations: dict[tuple, int] = {}
        # fresh per-kind crash-spec queues: every cell re-covers the points
        # its process census can fire
        census = self._census(sc)
        self._spec_queues = {}
        for kind, spec in cfg.scripted_kills:
            if kind in census:
                self._spec_queues.setdefault(kind, []).append(spec)

        coordinator = client = None
        if sc.cluster:
            ccfg = ClusterConfig(
                workers=cfg.cluster_workers,
                buckets=max(sc.bucket, 1),
                round_rows=cfg.round_rows,
                compaction=True,
                serve=True,
                seed=cfg.seed,
            )
            coordinator = ClusterCoordinator(cell["table_uri"], ccfg).start()
            coordinator.go_event.set()
            client = ClusterClient(table, coordinator.host, coordinator.port)
        cell["coordinator"] = coordinator
        gateway = Gateway(table, catalog=catalog, client=client)
        server = GatewayServer(gateway).start()
        cell["gateway"], cell["server"] = gateway, server

        rng = np.random.default_rng(cfg.seed * 31 + 17 + len(self.cells))
        t_start = time.monotonic()
        deadline = t_start + cfg.duration_s
        cell["deadline"] = deadline
        for kind, n in census.items():
            for i in range(n):
                self._spawn_child(cell, kind, i)
        threads = [
            threading.Thread(
                target=self._churn_loop, args=(cell, deadline), name="mega-churn", daemon=True
            ),
            threading.Thread(
                target=self._gw_subscriber_loop,
                args=(cell, deadline),
                name="mega-gwsub",
                daemon=True,
            ),
        ]
        if sc.cluster:
            threads.append(
                threading.Thread(
                    target=self._elastic_loop,
                    args=(cell, t_start, deadline),
                    name="mega-elastic",
                    daemon=True,
                )
            )
        for t in threads:
            t.start()

        killable = [k for k in ("writer", "worker", "gateway-writer", "subscriber") if k in census]
        next_kill = (
            t_start + float(rng.uniform(0.5, 1.5)) * cfg.kill_period_s
            if (cfg.kill_period_s > 0 and killable)
            else float("inf")
        )
        try:
            while time.monotonic() < deadline:
                for (kind, idx), (p, spec) in list(self._procs.items()):
                    rc = p.poll()
                    if rc is None:
                        continue
                    self._reap(cell, kind, idx, rc, spec)
                    if (kind, idx) in cell["no_respawn"]:
                        del self._procs[(kind, idx)]  # planned retire: gone for good
                        continue
                    self._spawn_child(cell, kind, idx)
                    self.counts["procs_respawned"] += 1
                now = time.monotonic()
                if now >= next_kill:
                    kind = killable[int(rng.integers(0, len(killable)))]
                    idx = int(rng.integers(0, census[kind]))
                    victim = self._procs.get((kind, idx))
                    if victim is not None and victim[0].poll() is None:
                        victim[0].kill()  # SIGKILL: reaped (and counted) next loop
                    next_kill = now + float(rng.uniform(0.5, 1.5)) * cfg.kill_period_s
                time.sleep(0.15)
        finally:
            # ---- drain -------------------------------------------------
            cell["stop"].set()
            with open(cell["stop_file"], "w") as f:
                f.write("stop")
            if coordinator is not None:
                coordinator.stop_event.set()
            drain_deadline = time.monotonic() + 90.0
            for (kind, idx), (p, spec) in list(self._procs.items()):
                timeout = max(1.0, drain_deadline - time.monotonic())
                try:
                    rc = p.wait(timeout=timeout)
                    if rc not in (0, None):
                        self._reap(cell, kind, idx, rc, spec)
                except subprocess.TimeoutExpired:
                    cell["errors"].append(f"{kind} {idx} failed to drain; killed")
                    p.kill()
                    p.wait(timeout=30)
            for t in threads:
                t.join(timeout=15)
            gateway.close()
            server.close()
            if client is not None:
                client.close()
            if coordinator is not None:
                coordinator.close()
        wall_s = time.monotonic() - t_start
        self._heal_chaos()
        report = self._verify_cell(cell, wall_s)
        self.cells.append(report)
        return report

    # ---- per-cell verification ----------------------------------------
    def _journals(self, cell) -> dict[str, str]:
        sc: MegaScenario = cell["scenario"]
        run_dir = cell["run_dir"]
        journals: dict[str, str] = {}
        if sc.schema == "kv":
            for w in range(sc.direct_writers):
                journals[f"psoak-w{w}"] = os.path.join(run_dir, f"direct-journal-{w}.jsonl")
        if sc.cluster:
            for w in range(self.cfg.cluster_workers):
                journals[f"cluster-w{w}"] = os.path.join(run_dir, f"cluster-journal-{w}.jsonl")
        for w in range(sc.gateway_writers):
            journals[f"{GW_USER_PREFIX}{w}"] = os.path.join(run_dir, f"gw-journal-{w}.jsonl")
        return journals

    def _verify_subscribers(self, cell, table) -> dict:
        """Each subscriber journal (CDC-format round-tripped rows) folds to
        exactly the pinned scan at its checkpoint — across kill -9s and
        at-least-once replays (sid-keyed overwrite)."""
        from ..types import RowKind

        sc: MegaScenario = cell["scenario"]
        out = {"sub_batches": 0, "sub_mismatches": 0, "sub_journals": 0}
        for i in range(sc.subscribers):
            path = os.path.join(cell["run_dir"], f"sub-{i}.jsonl")
            events = WriterJournal.read(path)
            by_sid: dict[int, tuple] = {}
            for rec in events:
                if "sid" in rec:
                    by_sid[rec["sid"]] = (rec["rows"], rec["kinds"])
            if not by_sid:
                cell["errors"].append(f"subscriber {i} journal recorded no batches")
                continue
            out["sub_journals"] += 1
            out["sub_batches"] += len(by_sid)
            checkpoint = max(by_sid)
            state: dict = {}
            for sid in sorted(by_sid):
                rows, kinds = by_sid[sid]
                for row, kind in zip(rows, kinds):
                    k = RowKind(int(kind))
                    if k in (RowKind.INSERT, RowKind.UPDATE_AFTER):
                        state[row[0]] = tuple(row)
                    elif k == RowKind.DELETE:
                        state.pop(row[0], None)
            try:
                pinned = table.copy({"scan.snapshot-id": str(checkpoint)})
                rb = pinned.new_read_builder()
                batch = rb.new_read().read_all(rb.new_scan().plan())
                expected = {row[0]: tuple(row) for row in batch.to_pylist()}
            except Exception:
                cell["errors"].append(
                    f"subscriber {i} pinned scan @{checkpoint} crashed:\n{traceback.format_exc()}"
                )
                continue
            if state != expected:
                out["sub_mismatches"] += 1
                missing = [k for k in expected if k not in state]
                extra = [k for k in state if k not in expected]
                cell["inconsistencies"].append(
                    {
                        "kind": "sub-journal-mismatch",
                        "subscriber": i,
                        "checkpoint": checkpoint,
                        "missing": len(missing),
                        "extra": len(extra),
                        "sample": (missing[:3], extra[:3]),
                    }
                )
        return out

    def _sql_battery(self, cell, table, expected: dict) -> dict:
        """Quiesced, healed-store SQL gate: every statement runs twice
        through a (local-route) gateway and once through sql.select.query —
        the three answers must be BIT-IDENTICAL — and count(*) must equal
        the fold's unique-key count."""
        from ..sql.select import query
        from .gateway import Gateway

        sc: MegaScenario = cell["scenario"]
        mismatches = 0
        stmts = _sql_statements(sc.schema, sc.table_ident, cluster=False)
        gw = Gateway(table, catalog=cell["catalog"])
        try:
            for stmt in stmts:
                try:
                    a = gw.sql(stmt, tenant="analytics").to_pylist()
                    b = gw.sql(stmt, tenant="analytics").to_pylist()
                    c = query(cell["catalog"], stmt).to_pylist()
                except Exception:
                    cell["errors"].append(
                        f"sql battery crashed on {stmt!r}:\n{traceback.format_exc()}"
                    )
                    continue
                if not (a == b == c):
                    mismatches += 1
                    cell["inconsistencies"].append(
                        {"kind": "sql-battery-mismatch", "stmt": stmt, "gw": a[:2], "local": c[:2]}
                    )
            try:
                n = query(cell["catalog"], f"SELECT count(*) FROM {sc.table_ident}").to_pylist()
                if int(n[0][0]) != len(expected):
                    mismatches += 1
                    cell["inconsistencies"].append(
                        {
                            "kind": "sql-count-vs-fold",
                            "sql": int(n[0][0]),
                            "fold": len(expected),
                        }
                    )
            except Exception:
                cell["errors"].append(f"sql count check crashed:\n{traceback.format_exc()}")
        finally:
            gw.close()
        return {"sql_battery_stmts": len(stmts), "sql_battery_mismatches": mismatches}

    def _verify_tag_branch(self, cell, table, landed: dict) -> dict:
        """Time travel agrees with history: the scan at the tag's snapshot
        (direct, SQL `FOR VERSION AS OF`, and the branch forked from the
        tag) equals the fold of landed rounds up to that snapshot."""
        from ..sql.select import query
        from ..table import load_table
        from .oracle import scan_rows

        sc: MegaScenario = cell["scenario"]
        out = {"tag_sid": None, "tag_mismatches": 0, "branch_rows": None}
        if not cell.get("tagged"):
            cell["errors"].append("branch_tag cell never created its tag")
            return out
        tags = table.tags()
        if "mega-v1" not in tags:
            cell["errors"].append(f"tag mega-v1 missing (tags: {sorted(tags)})")
            return out
        tag_sid = tags["mega-v1"]
        out["tag_sid"] = tag_sid
        expected_at_tag: dict = {}
        for sid in sorted(landed):
            if sid <= tag_sid:
                expected_at_tag.update(landed[sid])
        try:
            got, _physical = scan_rows(table, tag_sid)
        except Exception:
            cell["errors"].append(f"tag scan crashed:\n{traceback.format_exc()}")
            return out
        if got != expected_at_tag:
            out["tag_mismatches"] += 1
            cell["inconsistencies"].append(
                {
                    "kind": "tag-scan-vs-fold",
                    "tag_sid": tag_sid,
                    "scan": len(got),
                    "fold": len(expected_at_tag),
                }
            )
        try:
            n = query(
                cell["catalog"],
                f"SELECT count(*) FROM {sc.table_ident} FOR VERSION AS OF 'mega-v1'",
            ).to_pylist()
            if int(n[0][0]) != len(expected_at_tag):
                out["tag_mismatches"] += 1
                cell["inconsistencies"].append(
                    {"kind": "time-travel-count", "sql": int(n[0][0]), "fold": len(expected_at_tag)}
                )
        except Exception:
            cell["errors"].append(f"time-travel SQL crashed:\n{traceback.format_exc()}")
        if cell.get("branched"):
            try:
                bt = load_table(
                    cell["table_uri"], commit_user="mega-verify", dynamic_options={"branch": "exp"}
                )
                bgot, _ = scan_rows(bt, bt.store.snapshot_manager.latest_snapshot_id())
                out["branch_rows"] = len(bgot)
                if bgot != expected_at_tag:
                    out["tag_mismatches"] += 1
                    cell["inconsistencies"].append(
                        {
                            "kind": "branch-scan-vs-fold",
                            "branch": len(bgot),
                            "fold": len(expected_at_tag),
                        }
                    )
            except Exception:
                cell["errors"].append(f"branch scan crashed:\n{traceback.format_exc()}")
        return out

    def _verify_consumer_expiry(self, cell, table) -> dict:
        from ..table.consumer import ConsumerManager

        sc: MegaScenario = cell["scenario"]
        out = {"expired_consumers": sorted(cell["expired_consumers"])}
        if not sc.consumer_expiry:
            return out
        live = ConsumerManager(table.file_io, table.path).list_consumers()
        if "mega-dead" not in cell["expired_consumers"]:
            cell["inconsistencies"].append(
                {"kind": "decoy-consumer-survived", "live": sorted(live)}
            )
        # a heartbeating subscriber must never be reaped by the expiry churn
        reaped_live = [
            c for c in cell["expired_consumers"] if c.startswith("mega-sub-")
        ]
        if reaped_live:
            cell["inconsistencies"].append(
                {"kind": "live-consumer-expired", "consumers": reaped_live}
            )
        return out

    def _verify_cell(self, cell, wall_s: float) -> dict:
        from ..metrics import gateway_metrics
        from ..table import load_table
        from .oracle import fold_landed_rounds, read_client_logs, verify_table_state

        sc: MegaScenario = cell["scenario"]
        run_dir = cell["run_dir"]
        table = load_table(cell["table_uri"], commit_user="mega-verify")
        untyped_before = cell.get("untyped_at_start", 0)
        decode = str if sc.schema == "dict" else int
        landed, stats = fold_landed_rounds(
            table.store,
            self._journals(cell),
            user_prefix=MEGA_USER_PREFIXES,
            inconsistencies=cell["inconsistencies"],
            decode_key=decode,
        )
        if sc.schema == "wide":
            # journal values are JSON lists; the scan yields tuples
            landed = {
                sid: {k: tuple(v) if isinstance(v, list) else v for k, v in rows.items()}
                for sid, rows in landed.items()
            }
        expected: dict = {}
        for sid in sorted(landed):
            expected.update(landed[sid])
        if stats["double_applied"]:
            cell["inconsistencies"].append(
                {"kind": "double-applied", "rounds": stats["double_applied"]}
            )
        # subscriber folds FIRST: their pinned checkpoints predate the
        # verification compaction's extra snapshots
        subs = self._verify_subscribers(cell, table)
        state = verify_table_state(
            table,
            expected,
            os.path.join(self.warehouse_posix, "mega.db", sc.table_ident.split(".", 1)[1]),
            cell["errors"],
            cell["inconsistencies"],
            sweep=True,
            force_writable=sc.cluster,
        )
        sql = self._sql_battery(cell, table, expected)
        tag = self._verify_tag_branch(cell, table, landed) if sc.branch_tag else {}
        consumers = self._verify_consumer_expiry(cell, table)
        reads = read_client_logs(
            [os.path.join(run_dir, f"reads-{r}.jsonl") for r in range(sc.readers)]
        )
        gets = read_client_logs(
            [os.path.join(run_dir, f"gets-{g}.jsonl") for g in range(sc.getters)]
        )
        sqlc = read_client_logs(
            [os.path.join(run_dir, f"sql-{c}.jsonl") for c in range(sc.sql_clients)]
        )
        untyped = gateway_metrics().counter("sheds_untyped").count - untyped_before
        consistent = (
            not cell["errors"]
            and not cell["inconsistencies"]
            and state["lost_rows"] == 0
            and state["duplicated_rows"] == 0
            and state["wrong_values"] == 0
            and state["record_count_matches"]
            and len(state["leaked_files"]) == 0
            and reads["read_errors"] == 0
            and gets["read_errors"] == 0
            and sqlc["read_errors"] == 0
            and subs["sub_mismatches"] == 0
            and sql["sql_battery_mismatches"] == 0
            and tag.get("tag_mismatches", 0) == 0
            and untyped == 0
        )
        return {
            "cell": sc.name,
            "schema": sc.schema,
            "bucket": sc.bucket,
            "cdc_format": sc.cdc_format,
            "cluster": sc.cluster,
            "wall_s": round(wall_s, 2),
            "consistent": consistent,
            "accepted_commits": len(landed),
            "expected_unique_keys": len(expected),
            "final_rows": state["final_rows"],
            "total_record_count": state["total_record_count"],
            "record_count_matches": state["record_count_matches"],
            "lost_rows": state["lost_rows"],
            "duplicated_rows": state["duplicated_rows"],
            "wrong_values": state["wrong_values"],
            "gw_sheds_untyped": untyped,
            "gw_sub_rows": cell.get("gw_sub_rows", 0),
            "churn_io_faults": cell.get("churn_io_faults", 0),
            **stats,
            **subs,
            **sql,
            **tag,
            **consumers,
            "pinned_reads_ok": reads["reads_ok"],
            "pinned_read_errors": reads["read_errors"],
            "getter_reads_ok": gets["reads_ok"],
            "getter_read_errors": gets["read_errors"],
            "sql_client_ok": sqlc["reads_ok"],
            "sql_client_errors": sqlc["read_errors"],
            "orphans_removed": state["orphans_removed"],
            "leaked_file_count": len(state["leaked_files"]),
            "leaked_files": state["leaked_files"][:10],
            "inconsistencies": cell["inconsistencies"][:10],
            "errors": cell["errors"][:5],
        }

    # ---- the matrix ----------------------------------------------------
    def run(self) -> dict:
        from ..metrics import gateway_metrics, registry

        os.makedirs(self.run_root, exist_ok=True)
        os.makedirs(self.warehouse_posix, exist_ok=True)
        t0 = time.monotonic()
        for sc in self.cfg.scenarios:
            # the untyped-shed gate is a per-cell DELTA of the process-global
            # counter — stash the baseline on the cell before it runs
            baseline = gateway_metrics().counter("sheds_untyped").count
            try:
                report = self.run_cell(sc)
            except Exception:
                self._heal_chaos()
                report = {
                    "cell": sc.name,
                    "consistent": False,
                    "errors": [f"cell crashed:\n{traceback.format_exc()}"],
                }
                self.cells.append(report)
            report.setdefault("gw_sheds_untyped", None)
            if report.get("gw_sheds_untyped") is None:
                report["gw_sheds_untyped"] = (
                    gateway_metrics().counter("sheds_untyped").count - baseline
                )
        from ..metrics import Counter, Gauge, Histogram

        groups: dict[str, int] = {}
        for (name, _tags), group in registry.groups.items():
            total = 0
            for m in group.metrics.values():
                if isinstance(m, (Counter, Histogram)):
                    total += m.count
                elif isinstance(m, Gauge) and m.value:
                    total += 1
            groups[name] = groups.get(name, 0) + total
        metric_census = {g: groups.get(g, 0) for g in METRIC_GROUPS}
        kinds_killed = sorted(k for k, v in self.kills_by_kind.items() if v > 0)
        points_fired = sorted(
            p for p, v in self.kills_by_point.items() if v > 0 and p != "random-sigkill"
        )
        return {
            "consistent": all(c.get("consistent") for c in self.cells),
            "wall_s": round(time.monotonic() - t0, 2),
            "cells": self.cells,
            "kills_total": self.counts["procs_killed"],
            "kills_by_kind": self.kills_by_kind,
            "kills_by_point": self.kills_by_point,
            "process_kinds_killed": kinds_killed,
            "crash_points_fired": points_fired,
            "metric_groups": metric_census,
            **self.counts,
        }


def run_mega_soak(base_dir: str, cfg: "MegaConfig | None" = None) -> dict:
    """Stand up the full stack per scenario cell under `base_dir` (one
    chaos warehouse), run the matrix, return the cross-plane report."""
    return MegaSoakSupervisor(base_dir, cfg).run()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def _gateway_writer_args(argv):
    import argparse

    ap = argparse.ArgumentParser(prog="mega_soak gateway-writer")
    ap.add_argument("--table", required=True)
    ap.add_argument("--gateway", required=True, help="host:port")
    ap.add_argument("--wid", type=int, required=True)
    ap.add_argument("--schema", default="kv", choices=("kv", "dict", "wide"))
    ap.add_argument("--journal", required=True)
    ap.add_argument("--stop-file", required=True, dest="stop_file")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--incarnation", type=int, default=0)
    ap.add_argument("--rows-per-commit", type=int, default=120, dest="rows_per_commit")
    ap.add_argument("--update-fraction", type=float, default=0.25, dest="update_fraction")
    ap.add_argument("--max-rounds", type=int, default=10**9, dest="max_rounds")
    ap.add_argument("--tenant", default="ingest")
    return ap.parse_args(argv)


def _getter_args(argv):
    import argparse

    ap = argparse.ArgumentParser(prog="mega_soak getter")
    ap.add_argument("--gateway", required=True)
    ap.add_argument("--gid", type=int, required=True)
    ap.add_argument("--schema", default="kv", choices=("kv", "dict", "wide"))
    ap.add_argument("--gw-writers", type=int, default=2, dest="gw_writers")
    ap.add_argument("--window", type=int, default=4000)
    ap.add_argument("--log", required=True)
    ap.add_argument("--stop-file", required=True, dest="stop_file")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tenant", default="serve")
    return ap.parse_args(argv)


def _sql_client_args(argv):
    import argparse

    ap = argparse.ArgumentParser(prog="mega_soak sql-client")
    ap.add_argument("--gateway", required=True)
    ap.add_argument("--cid", type=int, required=True)
    ap.add_argument("--schema", default="kv", choices=("kv", "dict", "wide"))
    ap.add_argument("--ident", required=True, help="catalog table identifier (db.table)")
    ap.add_argument("--cluster", action="store_true")
    ap.add_argument("--log", required=True)
    ap.add_argument("--stop-file", required=True, dest="stop_file")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tenant", default="analytics")
    return ap.parse_args(argv)


def main(argv: "list[str] | None" = None) -> int:
    import argparse
    import tempfile

    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == "gateway-writer":
        return gateway_writer_main(_gateway_writer_args(argv[1:]))
    if argv and argv[0] == "getter":
        return getter_main(_getter_args(argv[1:]))
    if argv and argv[0] == "sql-client":
        return sql_client_main(_sql_client_args(argv[1:]))

    ap = argparse.ArgumentParser(description="paimon-tpu production mega-soak")
    ap.add_argument("base_dir", nargs="?", default=None)
    ap.add_argument("--duration", type=float, default=45.0, help="seconds per scenario cell")
    ap.add_argument("--workers", type=int, default=2, help="cluster workers (cluster cells)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--cells",
        default="",
        help="comma-separated cell names to run (default: the full matrix)",
    )
    ap.add_argument("--kill-period", type=float, default=9.0, help="mean s between random SIGKILLs (0=off)")
    ap.add_argument("--chaos-read-ms", type=float, default=1.0)
    ap.add_argument("--chaos-write-ms", type=float, default=0.5)
    ap.add_argument("--chaos-possibility", type=int, default=200, help="one op in N faults (0=off)")
    ap.add_argument("--min-kills", type=int, default=0, help="fail unless >= N kills were survived")
    ap.add_argument("--min-kill-kinds", type=int, default=0, help="fail unless >= N distinct process kinds died")
    args = ap.parse_args(argv)
    base = args.base_dir or tempfile.mkdtemp(prefix="paimon_mega_soak_")
    scenarios = DEFAULT_MATRIX
    if args.cells:
        wanted = {c.strip() for c in args.cells.split(",") if c.strip()}
        unknown = wanted - {s.name for s in DEFAULT_MATRIX}
        if unknown:
            print(f"unknown cells: {sorted(unknown)}", file=sys.stderr)
            return 2
        scenarios = tuple(s for s in DEFAULT_MATRIX if s.name in wanted)
    cfg = MegaConfig(
        duration_s=args.duration,
        cluster_workers=args.workers,
        seed=args.seed,
        scenarios=scenarios,
        kill_period_s=args.kill_period,
        chaos_read_ms=args.chaos_read_ms,
        chaos_write_ms=args.chaos_write_ms,
        chaos_possibility=args.chaos_possibility,
    )
    report = run_mega_soak(base, cfg)
    print(json.dumps(report, indent=2, default=str))
    ok = report["consistent"]
    if report["kills_total"] < args.min_kills:
        ok = False
        print(
            f"FAIL: only {report['kills_total']} kills survived (expected >= {args.min_kills})",
            file=sys.stderr,
        )
    if len(report["process_kinds_killed"]) < args.min_kill_kinds:
        ok = False
        print(
            f"FAIL: only {report['process_kinds_killed']} process kinds died "
            f"(expected >= {args.min_kill_kinds})",
            file=sys.stderr,
        )
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
