#!/usr/bin/env python
"""Benchmark: mesh-sharded execution (merge.engine = mesh) scaling over
simulated device counts.

Three table-level workloads — merge-read, full compaction, sort-compact —
run at 1/2/4/8 devices, each device count in its OWN subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=<d>`` (jax fixes the
device count at backend init, so scaling points can't share a process; the
same mechanism __graft_entry__'s dryrun uses). At every point the mesh
output is asserted BIT-IDENTICAL to the single-engine path before any time
is recorded; at 1 device the mesh engine exercises its cpu fallback, so the
"1 device" row doubles as the degradation guard.

Storage sits behind fs/testing.LatencyFileIO (fixed first-byte latency per
object read — the object-store shape). That is the resource the mesh layer
actually scales on this 1-core CI rig: the host-side feeder opens one
prefetch lane per device, so 8 devices pay the per-file RTT ~8 splits at a
time while the batched shard_map merges run; real chips add compute scaling
on top (each virtual CPU device here shares the single core, so device math
can only tie). Headline: merge-read wall at 8 devices >= 3x the 1-device
wall on the 8-bucket scan.

Rows land in benchmarks/results/multichip_bench.json; run_headline() is the
bench.py entry point (spawns only the 1- and 8-device children).
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

N_BUCKETS = 8
N_RUNS = int(os.environ.get("PAIMON_TPU_MULTICHIP_RUNS", "6"))
# x N_RUNS overlapping runs: a real k-way merge, IO-bound
ROWS_PER_RUN = int(os.environ.get("PAIMON_TPU_MULTICHIP_ROWS", "4000"))
STORE_RTT_MS = float(os.environ.get("PAIMON_TPU_MULTICHIP_RTT_MS", "90"))
SORT_ROWS = int(os.environ.get("PAIMON_TPU_MULTICHIP_SORT_ROWS", "24000"))
DEVICE_COUNTS = (1, 2, 4, 8)
RESULTS = os.path.join(HERE, "results", "multichip_bench.json")


# ---------------------------------------------------------------------------
# child: one device count, one process
# ---------------------------------------------------------------------------


def _build_pk_table(cat, name: str, engine: str):
    import numpy as np

    import paimon_tpu as pt

    schema = pt.RowType.of(
        ("id", pt.BIGINT(False)), ("c1", pt.BIGINT()), ("d1", pt.DOUBLE()), ("s1", pt.STRING())
    )
    table = cat.create_table(
        f"bench.{name}",
        schema,
        primary_keys=["id"],
        options={
            "bucket": str(N_BUCKETS),
            "write-only": "true",  # keep runs overlapping: real k-way merges
            "merge.engine": engine,
            "sort-engine": "xla-segmented",  # pin the device kernel on CPU
            # manifest cache ON (the PR 1 production default — planning RTT
            # is paid once, not per iteration), data-file cache OFF so every
            # timed scan re-fetches and re-decodes the data bytes cold
            "cache.data-file.max-memory-size": "0 b",
        },
    )
    rng = np.random.default_rng(23)
    total = ROWS_PER_RUN * N_RUNS
    ids = rng.permutation(total).astype(np.int64)
    for r in range(N_RUNS):
        chunk = np.sort(ids[r * ROWS_PER_RUN : (r + 1) * ROWS_PER_RUN])
        wb = table.new_batch_write_builder()
        w = wb.new_write()
        w.write(
            {
                "id": chunk,
                "c1": chunk * 3,
                "d1": chunk.astype(np.float64) * 0.5,
                "s1": np.array([f"v-{int(x) % 997:04d}" for x in chunk], dtype=object),
            }
        )
        wb.new_commit().commit(w.prepare_commit())
    return table


def _assert_identical(a, b):
    import numpy as np

    assert a.num_rows == b.num_rows, (a.num_rows, b.num_rows)
    for name in a.schema.field_names:
        assert np.array_equal(a.column(name).values, b.column(name).values), name
        assert np.array_equal(a.column(name).validity, b.column(name).validity), name


def _cold_read(table):
    # data bytes cold on every pass; the decoded-manifest cache stays warm
    # (see _build_pk_table) so the timed region is the scan, not planning
    from paimon_tpu.utils.cache import data_file_cache

    data_file_cache().clear()
    t0 = time.perf_counter()
    rb = table.new_read_builder()
    out = rb.new_read().read_all(rb.new_scan().plan())
    return time.perf_counter() - t0, out


def _bench_merge_read(slow_table, iters: int) -> dict:
    mesh = slow_table.copy({"merge.engine": "mesh"})
    single = slow_table.copy({"merge.engine": "single"})
    _cold_read(mesh)  # warm jit caches outside the timed region
    best_mesh = best_single = float("inf")
    for _ in range(iters):
        dt, out_m = _cold_read(mesh)
        best_mesh = min(best_mesh, dt)
        dt, out_s = _cold_read(single)
        best_single = min(best_single, dt)
        _assert_identical(out_m, out_s)  # every pass, before times count
    rows = out_m.num_rows
    return {
        "workload": "merge-read",
        "rows": rows,
        "mesh_ms": round(best_mesh * 1000, 1),
        "single_ms": round(best_single * 1000, 1),
        "rows_per_sec_mesh": round(rows / best_mesh, 1),
    }


def _bench_compaction(root: str, rtt_ms: float) -> dict:
    """Full compaction wall, mesh vs single, each on its OWN freshly built
    table (compaction mutates the LSM — the two engines can't share one)."""
    from paimon_tpu.catalog import FileSystemCatalog
    from paimon_tpu.fs.testing import LatencyFileIO
    from paimon_tpu.table import load_table

    out = {}
    readbacks = {}
    for engine in ("mesh", "single"):
        cat = FileSystemCatalog(os.path.join(root, f"compact_{engine}"), commit_user="bench")
        table = _build_pk_table(cat, f"compact_{engine}", engine)
        slow = load_table(f"latency://{table.path}", commit_user="bench")
        # the build table is write-only (keeps runs overlapping); the compact
        # job itself must run with compaction enabled
        slow = slow.copy({"merge.engine": engine, "write-only": "false"})
        t0 = time.perf_counter()
        wb = slow.new_batch_write_builder()
        w = wb.new_write()
        w.compact(full=True)
        wb.new_commit().commit(w.prepare_commit())
        out[engine] = time.perf_counter() - t0
        _, readbacks[engine] = _cold_read(slow)
    _assert_identical(readbacks["mesh"], readbacks["single"])
    return {
        "workload": "compaction",
        "mesh_ms": round(out["mesh"] * 1000, 1),
        "single_ms": round(out["single"] * 1000, 1),
    }


def _bench_sort_compact(root: str) -> dict:
    import numpy as np

    import paimon_tpu as pt
    from paimon_tpu.catalog import FileSystemCatalog
    from paimon_tpu.fs.testing import LatencyFileIO
    from paimon_tpu.table import load_table
    from paimon_tpu.table.sort_compact import sort_compact

    schema = pt.RowType.of(("x", pt.BIGINT(False)), ("y", pt.BIGINT()), ("s", pt.STRING()))
    out = {}
    readbacks = {}
    rng_seed = 31
    for engine in ("mesh", "single"):
        cat = FileSystemCatalog(os.path.join(root, f"sc_{engine}"), commit_user="bench")
        table = cat.create_table(
            f"bench.sc_{engine}",
            schema,
            options={
                "bucket": "4",
                "merge.engine": engine,
                "sort-engine": "xla-segmented",
                "parallel.key-axis.rows": "4096",
                "cache.manifest.max-memory-size": "0 b",
                "cache.data-file.max-memory-size": "0 b",
            },
        )
        rng = np.random.default_rng(rng_seed)
        per = SORT_ROWS // 3
        for r in range(3):  # 3 files per bucket: real multi-file input IO
            x = rng.integers(0, 1 << 40, per).astype(np.int64)
            wb = table.new_batch_write_builder()
            w = wb.new_write()
            w.write(
                {
                    "x": x,
                    "y": (x * 13) % 100_003,
                    "s": np.array([f"s{int(v) % 211}" for v in x], dtype=object),
                }
            )
            wb.new_commit().commit(w.prepare_commit())
        slow = load_table(f"latency://{table.path}", commit_user="bench").copy(
            {"merge.engine": engine}
        )
        # pass 1 warms the jit caches (key-axis kernel shapes are pow2-
        # padded, so the timed second pass reuses every compile)
        n = sort_compact(slow, ["y", "x"], order="zorder")
        assert n == 3 * per, n
        t0 = time.perf_counter()
        n = sort_compact(slow, ["y", "x"], order="zorder")
        out[engine] = time.perf_counter() - t0
        assert n == 3 * per, n
        _, readbacks[engine] = _cold_read(slow)
    _assert_identical(readbacks["mesh"], readbacks["single"])
    return {
        "workload": "sort-compact",
        "rows": SORT_ROWS,
        "mesh_ms": round(out["mesh"] * 1000, 1),
        "single_ms": round(out["single"] * 1000, 1),
    }


def child_main(n_devices: int, workloads: str, iters: int) -> None:
    import jax

    assert len(jax.devices()) == n_devices, (len(jax.devices()), n_devices)
    from paimon_tpu.fs.testing import LatencyFileIO
    from paimon_tpu.metrics import mesh_metrics
    from paimon_tpu.table import load_table

    tmp = tempfile.mkdtemp(prefix="paimon_tpu_multichip_")
    rows = []
    try:
        LatencyFileIO.configure(read_ms=STORE_RTT_MS)
        try:
            if "read" in workloads:
                from paimon_tpu.catalog import FileSystemCatalog

                cat = FileSystemCatalog(os.path.join(tmp, "read"), commit_user="bench")
                table = _build_pk_table(cat, "read", "mesh")
                slow = load_table(f"latency://{table.path}", commit_user="bench")
                rows.append(_bench_merge_read(slow, iters))
            if "compact" in workloads:
                rows.append(_bench_compaction(tmp, STORE_RTT_MS))
            if "sortcompact" in workloads:
                rows.append(_bench_sort_compact(tmp))
        finally:
            LatencyFileIO.configure()
        g = mesh_metrics()
        breakdown = {
            k: g.counter(k).count
            for k in ("buckets_sharded", "shards", "pad_rows", "exchange_rows")
        }
        print(
            json.dumps(
                {
                    "devices": n_devices,
                    "rtt_ms": STORE_RTT_MS,
                    "rows": rows,
                    "mesh_counters": breakdown,
                }
            )
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# ---------------------------------------------------------------------------
# parent: one subprocess per device count
# ---------------------------------------------------------------------------


def _spawn(n_devices: int, workloads: str = "read,compact,sortcompact", iters: int = 2) -> dict:
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    flags = " ".join(
        f for f in flags.split() if not f.startswith("--xla_force_host_platform_device_count")
    )
    env["XLA_FLAGS"] = (flags + f" --xla_force_host_platform_device_count={n_devices}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    # pin the device merge kernels (the CPU-adaptive default would route the
    # whole bench through the host lexsort and measure nothing mesh-shaped),
    # and size the shared decode pool for one IO lane per device
    env["PAIMON_TPU_FORCE_DEVICE_ENGINE"] = "1"
    # one IO lane per device x files per split: the reads of every in-flight
    # split must be able to sleep their RTT concurrently (applies to both
    # engines equally — the single path simply has fewer lanes to fill)
    env.setdefault("PAIMON_TPU_SHARED_POOL_WORKERS", "64")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", str(n_devices), workloads, str(iters)],
        env=env,
        cwd=os.path.dirname(HERE),
        capture_output=True,
        text=True,
        timeout=1800,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"multichip child (devices={n_devices}) failed rc={proc.returncode}:\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _scaling_rows(points: list[dict]) -> list[dict]:
    """Fold per-device child outputs into one row per workload."""
    by_workload: dict[str, dict] = {}
    for pt_ in points:
        for row in pt_["rows"]:
            w = by_workload.setdefault(
                row["workload"], {"metric": f"multichip {row['workload']} scaling", "unit": "ms"}
            )
            w[f"mesh_ms@{pt_['devices']}dev"] = row["mesh_ms"]
            w.setdefault("rows", row.get("rows"))
    base_dev = min(p["devices"] for p in points)
    top_dev = max(p["devices"] for p in points)
    for w in by_workload.values():
        base = w.get(f"mesh_ms@{base_dev}dev")
        top = w.get(f"mesh_ms@{top_dev}dev")
        if base and top:
            w["scaling"] = round(base / top, 2)
            w["scaling_devices"] = f"{top_dev} vs {base_dev}"
    return list(by_workload.values())


def run_headline(iters: int = 2) -> list[dict]:
    """bench.py entry: the 8-vs-1-device merge-read scaling headline plus
    the mesh counter breakdown (spawns two children; every pass asserts
    mesh == single bit-identically before timing counts)."""
    points = [_spawn(d, workloads="read", iters=iters) for d in (1, 8)]
    rows = _scaling_rows(points)
    top = points[-1]
    rows.append(
        {
            "metric": "mesh execution breakdown (8 devices)",
            **top["mesh_counters"],
            "unit": "counters",
        }
    )
    return rows


def main():
    points = [_spawn(d) for d in DEVICE_COUNTS]
    rows = _scaling_rows(points)
    payload = {"rtt_ms": STORE_RTT_MS, "points": points, "rows": rows}
    for row in rows:
        row["cores"] = os.cpu_count()
        print(json.dumps(row))
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    with open(RESULTS, "w") as f:
        json.dump(payload, f, indent=1)
    read_row = next(r for r in rows if "merge-read" in r["metric"])
    assert read_row["scaling"] >= 3.0, (
        f"merge-read scaling {read_row['scaling']} < 3x at 8 devices"
    )


if __name__ == "__main__":
    from paimon_tpu.utils import enable_compile_cache

    enable_compile_cache()
    if len(sys.argv) >= 2 and sys.argv[1] == "--child":
        child_main(int(sys.argv[2]), sys.argv[3], int(sys.argv[4]))
    else:
        main()
