#!/usr/bin/env python
"""Benchmark: cluster coordinator/worker scaling (service.cluster).

Aggregate ingest + merge-read rows/s at 1/2/4 worker OS processes, each
worker a private jax runtime with 2 forced-host virtual devices running
merge.engine=mesh over its bucket shard. The coordinator runs in THIS
process and is the only committer; workers ship CommitMessages over the
cluster RPC.

Storage sits behind fs/testing.LatencyFileIO in the WORKERS only (the data
plane pays object-store RTT; the committer's metadata writes stay local —
the single-parallelism committer is deliberately cheap, exactly the
reference topology where task managers stream to S3 while the committer
touches only manifests). On this 1-core CI rig the per-file RTT is the
resource worker processes scale on: W workers sleep their read RTTs
concurrently, and within each worker the mesh feeder overlaps one prefetch
lane per device. Real chips add compute scaling on top.

Every run asserts correctness before any time counts:
  * each worker's timed merge-read digest is identical across passes, and
  * equals the digest of a SINGLE-PROCESS oracle table built from the same
    deterministic per-(bucket, round) rows — final cluster table state is
    bit-identical to the oracle, at every worker count.

Headline (asserted in main): aggregate rows/s at 4 workers >= 2.5x 1 worker.
Results land in benchmarks/results/cluster_bench.json.
"""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

N_BUCKETS = 8
ROUNDS = int(os.environ.get("PAIMON_TPU_CLUSTER_BENCH_ROUNDS", "4"))
ROWS_PER_BUCKET = int(os.environ.get("PAIMON_TPU_CLUSTER_BENCH_ROWS", "100"))
READ_ITERS = int(os.environ.get("PAIMON_TPU_CLUSTER_BENCH_READS", "8"))
RTT_READ_MS = float(os.environ.get("PAIMON_TPU_CLUSTER_BENCH_RTT_MS", "200"))
RTT_WRITE_MS = float(os.environ.get("PAIMON_TPU_CLUSTER_BENCH_WRITE_RTT_MS", "5"))
DEVICES_PER_WORKER = 2
WORKER_COUNTS = (1, 2, 4)
RESULTS = os.path.join(HERE, "results", "cluster_bench.json")

TABLE_OPTIONS = {
    "bucket": str(N_BUCKETS),
    "write-only": "true",
    "merge.engine": "mesh",
    "sort-engine": "xla-segmented",
    "write-buffer-rows": str(ROWS_PER_BUCKET * N_BUCKETS * 2),
    # data bytes cold on every timed pass; decoded manifests stay warm
    "cache.data-file.max-memory-size": "0 b",
}


def _create_table(root: str) -> None:
    from paimon_tpu.core.schema import SchemaManager
    from paimon_tpu.fs import get_file_io
    from paimon_tpu.service.soak import SCHEMA

    SchemaManager(get_file_io(root), root).create_table(
        SCHEMA, primary_keys=["k"], options=TABLE_OPTIONS
    )


def _child_env(devices: int) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    flags = " ".join(
        f for f in flags.split() if not f.startswith("--xla_force_host_platform_device_count")
    )
    env["XLA_FLAGS"] = (flags + f" --xla_force_host_platform_device_count={devices}").strip()
    env["PAIMON_TPU_CLUSTER_ROLE"] = "worker"
    # one IO lane per device PER WORKER HOST (the multichip_bench rule): a
    # worker models one host whose store concurrency is bounded by its own
    # device count — aggregate IO lanes then grow with worker processes,
    # which is exactly the axis this bench measures
    env["PAIMON_TPU_SHARED_POOL_WORKERS"] = str(devices)
    env["PYTHONPATH"] = os.path.dirname(HERE) + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    return env


def _oracle_digests(root: str, bucket_sets: list[list[int]]) -> tuple[dict, int]:
    """Build the single-process oracle (same deterministic rows: round r
    writes pools[b] with v = r*1000 + k%997 for EVERY bucket, rounds
    0..ROUNDS) and digest each worker's bucket set the way the worker does."""
    import numpy as np

    from paimon_tpu.core.manifest import ManifestCommittable
    from paimon_tpu.service.cluster import bucket_key_pools
    from paimon_tpu.service.soak import SCHEMA
    from paimon_tpu.table import load_table
    from paimon_tpu.table.write import TableWrite

    oroot = root + "_oracle"
    _create_table(oroot)
    t = load_table(oroot, commit_user="oracle")
    pools = bucket_key_pools(N_BUCKETS, 0, ROWS_PER_BUCKET)
    for r in range(ROUNDS + 1):  # the workers' warm round 0 + timed 1..ROUNDS
        ks = [k for b in range(N_BUCKETS) for k in pools[b].tolist()]
        vs = [float(r * 1000 + (k % 997)) for k in ks]
        tw = TableWrite(t)
        tw.write({"k": ks, "v": vs})
        msgs = tw.prepare_commit()
        tw.close()
        t.store.new_commit().commit(ManifestCommittable(r + 1, messages=msgs))
    digests = {}
    total_rows = 0
    for buckets in bucket_sets:
        rb = t.new_read_builder()
        splits = [s for s in rb.new_scan().plan() if s.bucket in set(buckets)]
        out = rb.new_read().read_all(splits)
        ks = np.asarray(out.column("k").values)
        vs = np.asarray(out.column("v").values)
        order = np.argsort(ks)
        digests[tuple(sorted(buckets))] = hashlib.sha256(
            ks[order].tobytes() + vs[order].tobytes()
        ).hexdigest()
        total_rows += out.num_rows
    return digests, total_rows


def run_point(workers: int, base: str) -> dict:
    from paimon_tpu.service.cluster import ClusterConfig, ClusterCoordinator

    root = os.path.join(base, f"cluster_w{workers}")
    _create_table(root)
    cfg = ClusterConfig(workers=workers, buckets=N_BUCKETS, compaction=False, serve=False)
    coord = ClusterCoordinator(root, cfg).start()
    procs = []
    logs = []
    try:
        for wid in range(workers):
            log = open(os.path.join(base, f"bench-w{workers}-{wid}.log"), "wb")
            logs.append(log)
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable, "-m", "paimon_tpu.service.cluster", "worker",
                        "--table", f"latency://{root}",
                        "--wid", str(wid),
                        "--coordinator", f"{coord.host}:{coord.port}",
                        "--mode", "bench",
                        "--rounds", str(ROUNDS),
                        "--read-iters", str(READ_ITERS),
                        "--round-rows", str(ROWS_PER_BUCKET),
                        "--expected-workers", str(workers),
                        "--devices", str(DEVICES_PER_WORKER),
                        "--rtt-read-ms", str(RTT_READ_MS),
                        "--rtt-write-ms", str(RTT_WRITE_MS),
                        "--no-serve",
                    ],
                    env=_child_env(DEVICES_PER_WORKER),
                    stdout=log,
                    stderr=subprocess.STDOUT,
                )
            )
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            with coord._lock:
                if sum(1 for s in coord._slots.values() if s.alive) == workers:
                    break
            time.sleep(0.1)
        coord.go_event.set()
        while not coord.all_done():
            if time.monotonic() > deadline + 600:
                raise RuntimeError(f"bench point workers={workers} timed out")
            for p in procs:
                if p.poll() not in (None, 0):
                    tail = open(logs[procs.index(p)].name, "rb").read()[-2000:]
                    raise RuntimeError(f"bench worker died rc={p.returncode}:\n{tail.decode(errors='replace')}")
            time.sleep(0.1)
        status = coord.handle("status", {})
        stats = {int(w): s["done"] for w, s in status["workers"].items()}
    finally:
        coord.stop_event.set()
        for p in procs:
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
        coord.close()
        for log in logs:
            log.close()
    bucket_sets = [st["buckets"] for st in stats.values()]
    digests, _ = _oracle_digests(root, bucket_sets)
    for wid, st in stats.items():
        want = digests[tuple(sorted(st["buckets"]))]
        assert st["digest"] == want, (
            f"worker {wid} final state diverged from the single-process oracle"
        )
    total_rows = sum(st["ingested"] + st["rows_read"] for st in stats.values())
    wall = max(st["wall_s"] for st in stats.values())
    return {
        "workers": workers,
        "devices_per_worker": DEVICES_PER_WORKER,
        "rows_ingested": sum(st["ingested"] for st in stats.values()),
        "rows_merge_read": sum(st["rows_read"] for st in stats.values()),
        "wall_s": round(wall, 3),
        "ingest_s_max": round(max(st.get("ingest_s", 0) for st in stats.values()), 3),
        "read_s_max": round(max(st.get("read_s", 0) for st in stats.values()), 3),
        "rows_per_sec": round(total_rows / wall, 1),
        "oracle_identical": True,
    }


def main() -> None:
    base = tempfile.mkdtemp(prefix="paimon_cluster_bench_")
    points = []
    try:
        for w in WORKER_COUNTS:
            pt = run_point(w, base)
            pt["cores"] = os.cpu_count()
            pt["rtt_read_ms"] = RTT_READ_MS
            print(json.dumps(pt))
            points.append(pt)
    finally:
        shutil.rmtree(base, ignore_errors=True)
    base_rate = points[0]["rows_per_sec"]
    top = points[-1]
    scaling = round(top["rows_per_sec"] / base_rate, 2)
    row = {
        "metric": "cluster aggregate ingest+merge-read scaling",
        "unit": "rows/s",
        **{f"rows_per_sec@{p['workers']}w": p["rows_per_sec"] for p in points},
        "scaling": scaling,
        "scaling_workers": f"{top['workers']} vs {points[0]['workers']}",
    }
    print(json.dumps(row))
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    with open(RESULTS, "w") as f:
        json.dump({"rtt_read_ms": RTT_READ_MS, "points": points, "row": row}, f, indent=1)
    assert scaling >= 2.5, f"cluster scaling {scaling} < 2.5x at {top['workers']} workers"


if __name__ == "__main__":
    from paimon_tpu.utils import enable_compile_cache

    enable_compile_cache()
    main()
