#!/usr/bin/env python
"""Benchmark: elastic cluster — live rescale, scale-out, replicated serving.

Three phases against in-process coordinator + worker agents (the TCP layer
is the same length-prefixed-JSON shim OS-process workers use; in-process
keeps the rig deterministic and the timings dominated by the injected
serve latency, not subprocess spawn noise):

1. rescale-under-load: 2 workers ingesting continuously over an 8-bucket
   table while serving probe threads measure routed-get latency; the
   coordinator drives a live 8 -> 16 mesh-repartition rescale mid-stream.
   Asserted: ZERO lost/duplicated rows (every journal-landed key present
   exactly once in the final scan) and serving p99 during the rescale
   window <= 2x the steady-state p99 — pinned readers keep serving the
   pre-rescale snapshot, so the window costs GIL overlap, not correctness.

2. scale-out 2 -> 4: two joiners register mid-stream (the join-steal range
   handoff), all four ingest to the end. Asserted: disjoint full bucket
   cover and ZERO lost/duplicated rows across the handoffs.

3. replicated serving for a hot shard: every get carries `delay-ms` of
   injected server latency and the client serializes calls per worker
   connection — the single-owner throughput ceiling is 1/delay. Once the
   heat EMA grants replicas (threshold crossed by the hammer itself), the
   round-robin owner ring multiplies that ceiling. Asserted: replicated
   get_batch throughput >= 2x the single-owner baseline, and every timed
   pass replica rows == primary rows == oracle (bit-identical serving is
   the precondition for counting the speedup at all).

Results land in benchmarks/results/elastic_bench.json.
"""

import json
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

# standalone runs get the forced-host virtual device mesh the cluster tests
# use; under bench.py jax is already configured and this is a no-op
if "jax" not in sys.modules:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

SERVE_DELAY_MS = float(os.environ.get("PAIMON_TPU_ELASTIC_BENCH_DELAY_MS", "10"))
REPLICA_DELAY_MS = float(os.environ.get("PAIMON_TPU_ELASTIC_BENCH_REP_DELAY_MS", "40"))
STEADY_S = float(os.environ.get("PAIMON_TPU_ELASTIC_BENCH_STEADY_S", "3"))
HAMMER_S = float(os.environ.get("PAIMON_TPU_ELASTIC_BENCH_HAMMER_S", "3"))
ROUND_ROWS = int(os.environ.get("PAIMON_TPU_ELASTIC_BENCH_ROWS", "64"))
RESULTS = os.path.join(HERE, "results", "elastic_bench.json")


def _mk_table(root: str, buckets: int, **extra) -> None:
    from paimon_tpu.core.schema import SchemaManager
    from paimon_tpu.fs import get_file_io
    from paimon_tpu.service.soak import SCHEMA

    opts = {
        "bucket": str(buckets),
        "write-only": "true",
        "merge.engine": "mesh",
        "write-buffer-rows": "128",
    }
    opts.update(extra)
    SchemaManager(get_file_io(root), root).create_table(SCHEMA, primary_keys=["k"], options=opts)


def _cluster(root: str, workers: int, buckets: int, serve_delay_ms: float, tmp: str):
    from paimon_tpu.service.cluster import ClusterClient, ClusterConfig, ClusterCoordinator, ClusterWorkerAgent
    from paimon_tpu.table import load_table

    coord = ClusterCoordinator(
        root, ClusterConfig(workers=workers, buckets=buckets, compaction=False)
    ).start()
    agents = []
    for wid in range(workers):
        a = ClusterWorkerAgent(
            wid, load_table(root, commit_user=f"cluster-w{wid}"),
            coord.host, coord.port,
            journal_path=os.path.join(tmp, f"journal-{os.path.basename(root)}-{wid}.jsonl"),
            round_rows=ROUND_ROWS, heartbeat_interval_s=0.1,
            serve=True, serve_delay_ms=serve_delay_ms,
        )
        a.register()
        a.start_heartbeats()
        agents.append(a)
    cli = ClusterClient(load_table(root, commit_user="bench-cli"), coord.host, coord.port)
    return coord, agents, cli


def _teardown(coord, agents, cli) -> None:
    cli.close()
    for a in agents:
        a.close()
    coord.close()


def _assert_no_lost_no_dup(root: str, agents) -> int:
    """Every journal-landed key appears EXACTLY once in the final scan (pk
    table: a duplicate would surface as an extra row, a loss as a missing
    key). Returns the row count."""
    from paimon_tpu.table import load_table

    rb = load_table(root, commit_user="verify").new_read_builder()
    out = rb.new_read().read_all(rb.new_scan().plan())
    got = out.column("k").values.tolist()
    landed = {k for a in agents for ks in a.landed_by_bucket.values() for k in ks}
    assert len(got) == len(set(got)), "duplicated primary keys in final scan"
    missing = landed - set(got)
    assert not missing, f"{len(missing)} landed rows lost (e.g. {sorted(missing)[:5]})"
    return len(got)


def _ingest_ok(a, deadline_s: float = 5.0) -> None:
    """Land one round, riding out the brief fencing window after a handoff
    or rescale (the poll-work resync reply carries the fresh assignment)."""
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        a.poll_and_compact()
        if a.ingest_round():
            return
        time.sleep(0.05)
    raise RuntimeError(f"worker {a.wid} could not land a round")


def _probe_loop(cli, keys, stop, out_ms, lock):
    i = 0
    while not stop.is_set():
        k = keys[i % len(keys)]
        t0 = time.perf_counter()
        cli.get_batch([k])
        ms = (time.perf_counter() - t0) * 1000
        with lock:
            out_ms.append(ms)
        i += 1


def phase_rescale(base: str) -> dict:
    """8 -> 16 live rescale under continuous ingest + serving probes."""
    root = os.path.join(base, "rescale")
    _mk_table(root, 8)
    coord, agents, cli = _cluster(root, 2, 8, SERVE_DELAY_MS, base)
    try:
        for a in agents:
            assert a.ingest_round()
        keys = [k for a in agents for ks in a.landed_by_bucket.values() for k in ks]
        ingest_stop = threading.Event()

        def ingest_loop():
            while not ingest_stop.is_set():
                for a in agents:
                    a.poll_and_compact()
                    a.ingest_round()
                time.sleep(0.02)

        ingester = threading.Thread(target=ingest_loop, daemon=True)
        ingester.start()
        lat_lock = threading.Lock()
        steady_ms: list = []
        stop = threading.Event()
        probes = [
            threading.Thread(
                target=_probe_loop, args=(cli, keys[i::2], stop, steady_ms, lat_lock), daemon=True
            )
            for i in range(2)
        ]
        for p in probes:
            p.start()
        time.sleep(STEADY_S)
        with lat_lock:
            baseline = list(steady_ms)
            steady_ms.clear()
        # the live rescale: the ingest loop's poll_and_compact executes the
        # rewrite tasks; probes keep serving off the pinned snapshot
        r = coord.start_rescale(16)
        assert r.get("started"), f"rescale refused: {r}"
        t0 = time.monotonic()
        while coord.handle("rescale_status", {})["active"]:
            if time.monotonic() - t0 > 120:
                raise RuntimeError("rescale did not complete")
            time.sleep(0.05)
        rescale_s = time.monotonic() - t0
        time.sleep(0.3)  # settle: routes republished, probes on the new layout
        with lat_lock:
            window = list(steady_ms)
        stop.set()
        for p in probes:
            p.join(timeout=10)
        ingest_stop.set()
        ingester.join(timeout=30)
        for a in agents:  # land a post-rescale round through the new routing
            _ingest_ok(a)
        assert coord.num_buckets == 16
        rows = _assert_no_lost_no_dup(root, agents)
        p99_steady = float(np.percentile(baseline, 99))
        p99_window = float(np.percentile(window, 99))
        assert p99_window <= 2.0 * p99_steady, (
            f"serving p99 {p99_window:.1f} ms during rescale > 2x steady {p99_steady:.1f} ms"
        )
        return {
            "metric": "live rescale 8->16 under load",
            "unit": "ms",
            "serve_delay_ms": SERVE_DELAY_MS,
            "rescale_wall_s": round(rescale_s, 2),
            "p99_steady_ms": round(p99_steady, 2),
            "p99_rescale_ms": round(p99_window, 2),
            "p99_ratio": round(p99_window / p99_steady, 2),
            "rows_final": rows,
            "lost_rows": 0,
            "duplicated_rows": 0,
        }
    finally:
        _teardown(coord, agents, cli)


def phase_scaleout(base: str) -> dict:
    """2 -> 4 workers mid-stream: join-steal handoffs, zero lost/dup."""
    from paimon_tpu.metrics import cluster_metrics
    from paimon_tpu.service.cluster import ClusterWorkerAgent
    from paimon_tpu.table import load_table

    root = os.path.join(base, "scaleout")
    _mk_table(root, 8)
    coord, agents, cli = _cluster(root, 2, 8, 0.0, base)
    try:
        handoffs0 = cluster_metrics().counter("handoffs").count
        t0 = time.monotonic()
        for _ in range(3):
            for a in agents:
                assert a.ingest_round()
        for wid in (2, 3):  # the joiners: register -> steal from the loaded pair
            a = ClusterWorkerAgent(
                wid, load_table(root, commit_user=f"cluster-w{wid}"),
                coord.host, coord.port,
                journal_path=os.path.join(base, f"journal-scaleout-{wid}.jsonl"),
                round_rows=ROUND_ROWS, heartbeat_interval_s=0.1, serve=True,
            )
            a.register()
            a.start_heartbeats()
            agents.append(a)
        owned = [b for w in range(4) for b in coord.assignment_of(w)[1]]
        assert sorted(owned) == list(range(8)), f"broken bucket cover after scale-out: {owned}"
        for _ in range(3):
            for a in agents:
                _ingest_ok(a)
        wall = time.monotonic() - t0
        rows = _assert_no_lost_no_dup(root, agents)
        return {
            "metric": "scale-out 2->4 under load",
            "unit": "rows/s",
            "rows_final": rows,
            "rows_per_sec": round(rows / wall, 1),
            "handoffs": cluster_metrics().counter("handoffs").count - handoffs0,
            "lost_rows": 0,
            "duplicated_rows": 0,
        }
    finally:
        _teardown(coord, agents, cli)


def _hammer_throughput(cli, keys, seconds: float, threads: int = 6) -> float:
    stop = threading.Event()
    counts = [0] * threads
    errs: list = []

    def loop(ti):
        i = 0
        while not stop.is_set():
            try:
                cli.get_batch([keys[i % len(keys)]])
            except Exception as e:  # noqa: BLE001
                errs.append(e)
                return
            counts[ti] += 1
            i += 1

    ts = [threading.Thread(target=loop, args=(ti,), daemon=True) for ti in range(threads)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    time.sleep(seconds)
    stop.set()
    for t in ts:
        t.join(timeout=10)
    if errs:
        raise errs[0]
    return sum(counts) / (time.perf_counter() - t0)


def phase_replica(base: str) -> dict:
    """Hot-shard serving throughput: single owner vs replicated ring. The
    injected per-get delay plus per-connection call serialization makes one
    owner a 1/delay ceiling; replicas multiply the ring."""
    hot = 0
    # baseline: replicas disabled
    root1 = os.path.join(base, "rep_single")
    _mk_table(root1, 4)
    coord1, agents1, cli1 = _cluster(root1, 3, 4, REPLICA_DELAY_MS, base)
    try:
        for a in agents1:
            assert a.ingest_round()
        keys = [k for a in agents1 for k in a.landed_by_bucket.get(hot, [])]
        assert keys
        single = _hammer_throughput(cli1, keys, HAMMER_S)
    finally:
        _teardown(coord1, agents1, cli1)

    # replicated: grant up to 2 replicas once the hammer's own heat crosses
    root2 = os.path.join(base, "rep_ring")
    _mk_table(
        root2, 4,
        **{
            "cluster.replica.heat-threshold": "1",
            "cluster.replica.interval": "100 ms",
            "cluster.replica.max-per-bucket": "2",
        },
    )
    coord2, agents2, cli2 = _cluster(root2, 3, 4, REPLICA_DELAY_MS, base)
    try:
        for a in agents2:
            assert a.ingest_round()
        keys = [k for a in agents2 for k in a.landed_by_bucket.get(hot, [])]
        assert keys
        from paimon_tpu.table import load_table
        from paimon_tpu.table.query import LocalTableQuery

        oracle = LocalTableQuery(load_table(root2, commit_user="oracle"))
        want = []
        for k in keys:
            d = oracle.lookup((), (k,))
            want.append(None if d is None else list(d.to_pylist()[0]))
        deadline = time.monotonic() + 60
        while len(cli2.replicas_of(hot)) < 2 and time.monotonic() < deadline:
            cli2.get_batch(keys)  # the hammer IS the heat source
            cli2.refresh_route()
        reps = cli2.replicas_of(hot)
        assert len(reps) >= 2, f"replicas never granted: {reps}"
        primary = cli2.owner_of(hot)
        # bit-identical serving across the whole ring, every timed pass
        wire_keys = [[k] for k in keys]
        for wid in (primary, *reps):
            rows = cli2._call(wid, "get_batch", keys=wire_keys, partition=[])["rows"]
            assert rows == want, f"owner {wid} diverged from the oracle"
        replicated = _hammer_throughput(cli2, keys, HAMMER_S)
        for wid in (primary, *reps):
            rows = cli2._call(wid, "get_batch", keys=wire_keys, partition=[])["rows"]
            assert rows == want, f"owner {wid} diverged after the timed pass"
    finally:
        _teardown(coord2, agents2, cli2)
    speedup = replicated / single
    assert speedup >= 2.0, f"replicated serving {speedup:.2f}x < 2x single-owner"
    return {
        "metric": "hot-bucket replicated serving throughput",
        "unit": "gets/s",
        "serve_delay_ms": REPLICA_DELAY_MS,
        "gets_per_sec_single": round(single, 1),
        "gets_per_sec_replicated": round(replicated, 1),
        "speedup": round(speedup, 2),
        "ring_size": 3,
        "replica_rows_bit_identical": True,
    }


def run_headline(iters: int = 1) -> list:
    """bench.py seam: one pass of every phase, returning the result rows."""
    rows = []
    base = tempfile.mkdtemp(prefix="paimon_elastic_bench_")
    try:
        rows.append(phase_rescale(base))
        rows.append(phase_scaleout(base))
        rows.append(phase_replica(base))
    finally:
        shutil.rmtree(base, ignore_errors=True)
    return rows


def main() -> None:
    rows = run_headline()
    for row in rows:
        print(json.dumps(row))
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    with open(RESULTS, "w") as f:
        json.dump(
            {
                "serve_delay_ms": SERVE_DELAY_MS,
                "replica_delay_ms": REPLICA_DELAY_MS,
                "cores": os.cpu_count(),
                "rows": rows,
            },
            f,
            indent=1,
        )


if __name__ == "__main__":
    from paimon_tpu.utils import enable_compile_cache

    enable_compile_cache()
    main()
