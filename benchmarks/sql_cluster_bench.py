#!/usr/bin/env python
"""Benchmark: distributed SQL scatter-gather scan scaling (sql.cluster).

One latency-shaped table (fs/testing.LatencyFileIO — every data/manifest
file open pays a simulated object-store RTT), aggregate GROUP BY queries
executed four ways: single-process `sql.query` reading THROUGH the
latency store, and `sql.cluster_query` against 1/2/4 serve-mode worker
OS processes. The worker data plane is where the RTT budget lives: each
worker scans only its owned buckets' splits and reduces them to ONE
partial aggregate on device (segment_reduce keyed on dictionary codes),
so W workers sleep their serial per-split RTTs concurrently and ship
back partial rows instead of scan rows. The coordinator combines
partials in the code domain (unify_pools + remap_codes + one more
segment_reduce) and runs the shared _finish tail. The coordinator's own
metadata plane (split planning) reads the plain local path — the
cluster_bench topology: data streams through the object store on the
workers while the coordinator keeps manifests cached locally.

Every timed pass asserts the distributed result BIT-IDENTICAL to the
single-process evaluator first (exactly-representable doubles make float
sums order-independent), and the cluster points additionally assert
sql{rows_reduced_device} grew — partials really reduced on workers.

Headline (asserted in main): aggregate-query speedup at 4 workers >= 3x
over 1 worker. Results land in benchmarks/results/sql_cluster_bench.json.
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

N_BUCKETS = 4
COMMITS = int(os.environ.get("PAIMON_TPU_SQLCB_COMMITS", "6"))
ROWS_PER_COMMIT = int(os.environ.get("PAIMON_TPU_SQLCB_ROWS", "8000"))
RTT_READ_MS = float(os.environ.get("PAIMON_TPU_SQLCB_RTT_MS", "250"))
ITERS = int(os.environ.get("PAIMON_TPU_SQLCB_ITERS", "3"))
WORKER_COUNTS = (1, 2, 4)
RESULTS = os.path.join(HERE, "results", "sql_cluster_bench.json")

QUERY = (
    "SELECT g, count(*), count(a), sum(a), min(b), max(b) FROM db.r "
    "GROUP BY g ORDER BY g"
)
SCALAR_QUERY = "SELECT count(*), sum(b), min(b), max(b) FROM db.r"

TABLE_OPTIONS = {
    "bucket": str(N_BUCKETS),
    "write-only": "true",
    # data bytes cold on every timed pass (each open pays the RTT); decoded
    # manifests warm after the untimed first iteration, so plan cost does
    # not smear the scan-scaling signal
    "cache.data-file.max-memory-size": "0 b",
    "cache.manifest.max-memory-size": "256 mb",
}


def _build(base: str):
    import numpy as np

    from paimon_tpu.catalog import FileSystemCatalog
    from paimon_tpu.types import BIGINT, DOUBLE, STRING, RowType

    cat = FileSystemCatalog(os.path.join(base, "wh"), commit_user="bench")
    t = cat.create_table(
        "db.r",
        RowType.of(("k", BIGINT(False)), ("a", BIGINT()), ("b", DOUBLE()), ("g", STRING())),
        primary_keys=["k"],
        options=TABLE_OPTIONS,
    )
    rng = np.random.default_rng(11)
    for r in range(COMMITS):
        ks = rng.choice(2 * ROWS_PER_COMMIT * COMMITS, size=ROWS_PER_COMMIT, replace=False)
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        w.write({
            "k": ks.tolist(),
            "a": [None if x % 13 == 0 else int(x % 997) for x in ks.tolist()],
            "b": (ks * 0.25 + r).tolist(),  # exactly representable: order-free sums
            "g": [f"g{int(x) % 7}" for x in ks.tolist()],
        })
        wb.new_commit().commit(w.prepare_commit())
    # the same physical files through the latency scheme: what the
    # single-process evaluator (whole engine behind the store) reads
    lat_cat = FileSystemCatalog("latency://" + os.path.join(base, "wh"), commit_user="bench")
    return cat, lat_cat, t


def _child_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PAIMON_TPU_CLUSTER_ROLE"] = "worker"
    env["PYTHONPATH"] = os.path.dirname(HERE) + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    return env


def _time_single(cat, want_rows: dict) -> float:
    from paimon_tpu.sql import query

    best = float("inf")
    for it in range(ITERS):
        t0 = time.perf_counter()
        outs = {q: query(cat, q).to_pylist() for q in want_rows}
        dt = time.perf_counter() - t0
        for q, rows in outs.items():
            assert rows == want_rows[q], f"single-process drift: {q}"
        if it > 0:
            best = min(best, dt)
    return best


def run_point(workers: int, cat, root: str, base: str, want_rows: dict) -> dict:
    """One cluster point: coordinator + client plan on the plain `root`;
    worker processes load `latency://root` so their scans pay the RTT."""
    from paimon_tpu.metrics import sql_metrics
    from paimon_tpu.service.cluster import ClusterClient, ClusterConfig, ClusterCoordinator
    from paimon_tpu.table import load_table

    coord = ClusterCoordinator(
        root, ClusterConfig(workers=workers, buckets=N_BUCKETS, compaction=False)
    ).start()
    procs, cli = [], None
    try:
        for wid in range(workers):
            log = open(os.path.join(base, f"sqlw{workers}-{wid}.log"), "wb")
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "paimon_tpu.service.cluster", "worker",
                 "--table", "latency://" + root, "--wid", str(wid),
                 "--coordinator", f"{coord.host}:{coord.port}",
                 "--mode", "serve", "--heartbeat-interval", "0.2",
                 "--rtt-read-ms", str(RTT_READ_MS)],
                stdout=log, stderr=subprocess.STDOUT, env=_child_env(),
            ))
            log.close()
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            for p in procs:
                if p.poll() not in (None,):
                    tail = open(os.path.join(base, f"sqlw{workers}-{procs.index(p)}.log"), "rb").read()[-2000:]
                    raise RuntimeError(f"worker died rc={p.returncode}:\n{tail.decode(errors='replace')}")
            try:
                cli = ClusterClient(load_table(root, commit_user="cli"), coord.host, coord.port)
                if len({cli.owner_of(b) for b in range(N_BUCKETS)}) == min(workers, N_BUCKETS):
                    break
                cli.close()
                cli = None
            except Exception:
                pass
            time.sleep(0.2)
        assert cli is not None, f"{workers} workers never registered serve ports"

        from paimon_tpu.sql import cluster_query

        g = sql_metrics()
        reduced0 = g.counter("rows_reduced_device").count
        best = float("inf")
        for it in range(ITERS):
            t0 = time.perf_counter()
            outs = {q: cluster_query(cat, q, cli).to_pylist() for q in want_rows}
            dt = time.perf_counter() - t0
            for q, rows in outs.items():
                assert rows == want_rows[q], f"{workers}w diverged from single-process: {q}"
            if it > 0:
                best = min(best, dt)
        reduced = g.counter("rows_reduced_device").count - reduced0
        assert reduced > 0, "no rows were reduced on workers"
        return {
            "workers": workers,
            "wall_s": round(best, 3),
            "queries_per_sec": round(len(want_rows) / best, 2),
            "rows_reduced_device": reduced,
            "identical_to_single_process": True,
        }
    finally:
        if cli is not None:
            cli.close()
        for p in procs:
            try:
                p.terminate()
                p.wait(timeout=30)
            except Exception:
                p.kill()
        coord.close()


def run(iters: int = ITERS) -> dict:
    """Full sweep: build, oracle, single-process timing, 1/2/4-worker
    cluster timings. Returns {points, single, row}."""
    global ITERS
    ITERS = iters
    from paimon_tpu.fs.testing import LatencyFileIO
    from paimon_tpu.sql import query

    base = tempfile.mkdtemp(prefix="paimon_sqlcluster_bench_")
    try:
        cat, lat_cat, t = _build(base)
        # the oracle rows: computed once on the plain path with NO latency,
        # asserted by every timed pass at every worker count
        want_rows = {q: query(cat, q).to_pylist() for q in (QUERY, SCALAR_QUERY)}
        LatencyFileIO.configure(read_ms=RTT_READ_MS, write_ms=0.0)
        try:
            single_s = _time_single(lat_cat, want_rows)
            points = [run_point(w, cat, t.path, base, want_rows) for w in WORKER_COUNTS]
        finally:
            LatencyFileIO.configure(read_ms=0.0, write_ms=0.0)
    finally:
        shutil.rmtree(base, ignore_errors=True)
    top = points[-1]
    speedup = round(points[0]["wall_s"] / top["wall_s"], 2)
    row = {
        "metric": "distributed SQL aggregate scan-fragment scaling (latency-shaped store)",
        "unit": "s/query-pair",
        "rtt_read_ms": RTT_READ_MS,
        "single_process_s": round(single_s, 3),
        **{f"wall_s@{p['workers']}w": p["wall_s"] for p in points},
        "speedup": speedup,
        "speedup_workers": f"{top['workers']}w vs {points[0]['workers']}w",
        "vs_single_process": round(single_s / top["wall_s"], 2),
        "identical_output": True,
    }
    return {"rtt_read_ms": RTT_READ_MS, "points": points, "single_process_s": round(single_s, 3), "row": row}


def run_headline(iters: int = 2) -> list:
    """bench.py hook: the sweep at reduced iterations, returning the rows
    it prints. The scaling floor is asserted by main(), not here — the
    headline row reports whatever this rig produced."""
    res = run(iters=iters)
    return [res["row"]]


def main() -> None:
    res = run()
    for p in res["points"]:
        print(json.dumps(p))
    print(json.dumps(res["row"]))
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    with open(RESULTS, "w") as f:
        json.dump(res, f, indent=1)
    speedup = res["row"]["speedup"]
    assert speedup >= 3.0, f"4-worker aggregate speedup {speedup} < 3x"


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from paimon_tpu.utils import enable_compile_cache

    enable_compile_cache()
    main()
