#!/usr/bin/env python
"""Benchmark: pipelined vs sequential multi-bucket cold scan.

An 8-bucket primary-key table, 4 overlapping sorted runs per bucket, read
cold (object caches off) two ways through the same Table API:

  sequential   scan.prefetch-splits = 0 — splits fetch, decode and merge
               strictly one after another (the pre-pipeline behavior)
  pipelined    scan.prefetch-splits = 2 (default) — split i+1 fetches bytes
               through RetryingFileIO and decodes on pipeline workers while
               split i merges on device (parallel/pipeline.py)

Two storage profiles per run:

  local        data on the local filesystem. On a multi-core host the decode
               of split i+1 overlaps split i's merge; on a single-core host
               (this rig: os.cpu_count() == 1) CPU-bound stages serialize and
               the pipeline can only tie — the row is the no-regression guard.
  store rtt    the same table behind fs/testing.LatencyFileIO, which charges
               a fixed first-byte latency per object read — the shape of a
               real object-store cold scan. This is what the pipeline is FOR:
               overlapped prefetches pay the RTT concurrently, a serial scan
               pays it once per file. Headline: >= 1.5x on 8 buckets.

Also checked every pass: output of both modes is bit-identical, and the
pipeline's queue-depth high-water stays <= prefetch+1 (the memory high-water
regression guard — readahead must not silently materialize the whole scan).

Prints one JSON line per row; the table also lands in
benchmarks/results/pipeline_bench.json.
"""

import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

N_BUCKETS = 8
N_RUNS = 4
ROWS_PER_RUN = 64_000  # x4 runs = 256k rows/bucket-set; decode-heavy but quick
STORE_RTT_MS = 8.0  # first-byte latency per object read (object-store shape)
PREFETCH = 2
RESULTS = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "results", "pipeline_bench.json"
)


def build_table(root: str, buckets: int = N_BUCKETS, rows_per_run: int = ROWS_PER_RUN):
    import paimon_tpu as pt
    from paimon_tpu.catalog import FileSystemCatalog

    cat = FileSystemCatalog(root, commit_user="bench")
    schema = pt.RowType.of(
        ("id", pt.BIGINT(False)),
        ("c1", pt.BIGINT()),
        ("d1", pt.DOUBLE()),
        ("s1", pt.STRING()),
    )
    table = cat.create_table(
        "bench.pipe",
        schema,
        primary_keys=["id"],
        options={
            "bucket": str(buckets),
            "file.format": "parquet",
            "write-only": "true",  # keep the runs overlapping: real k-way merge
            # caches off so every timed scan is genuinely cold
            "cache.manifest.max-memory-size": "0 b",
            "cache.data-file.max-memory-size": "0 b",
        },
    )
    rng = np.random.default_rng(17)
    total = rows_per_run * N_RUNS
    ids = rng.permutation(total).astype(np.int64)
    for r in range(N_RUNS):
        chunk = np.sort(ids[r * rows_per_run : (r + 1) * rows_per_run])
        wb = table.new_batch_write_builder()
        w = wb.new_write()
        w.write(
            {
                "id": chunk,
                "c1": chunk * 3,
                "d1": chunk.astype(np.float64) * 0.5,
                "s1": np.array([f"val-{int(x) % 997:04d}" for x in chunk], dtype=object),
            }
        )
        wb.new_commit().commit(w.prepare_commit())
    return table


def cold_scan(table, expect_rows: int) -> tuple[float, object]:
    from paimon_tpu.utils import cache as cache_mod

    cache_mod.clear_all()
    t0 = time.perf_counter()
    rb = table.new_read_builder()
    out = rb.new_read().read_all(rb.new_scan().plan())
    dt = time.perf_counter() - t0
    assert out.num_rows == expect_rows, out.num_rows
    return dt, out


def assert_bit_identical(a, b) -> None:
    for name in a.schema.field_names:
        assert np.array_equal(a.column(name).values, b.column(name).values), name
        assert np.array_equal(a.column(name).validity, b.column(name).validity), name


def run_profile(table, label: str, expect_rows: int, iters: int = 3) -> dict:
    from paimon_tpu.metrics import pipeline_metrics, registry

    seq = table.copy({"scan.prefetch-splits": "0"})
    pipe = table.copy({"scan.prefetch-splits": str(PREFETCH)})
    # warm jit caches once outside the timed region
    cold_scan(seq, expect_rows)
    best_seq, best_pipe = float("inf"), float("inf")
    out_seq = out_pipe = None
    registry.reset()
    for _ in range(iters):
        dt, out_seq = cold_scan(seq, expect_rows)
        best_seq = min(best_seq, dt)
        dt, out_pipe = cold_scan(pipe, expect_rows)
        best_pipe = min(best_pipe, dt)
    assert_bit_identical(out_seq, out_pipe)
    g = pipeline_metrics()
    high_water = g.gauge("queue_depth_high_water").value
    # memory high-water regression guard: bounded readahead means at most
    # prefetch+1 splits' decoded batches in flight, never the whole scan
    assert high_water <= PREFETCH + 1, high_water
    return {
        "metric": f"pipelined 8-bucket cold scan ({label})",
        "sequential_ms": round(best_seq * 1000, 1),
        "pipelined_ms": round(best_pipe * 1000, 1),
        "speedup": round(best_seq / best_pipe, 2),
        "splits_prefetched": g.counter("splits_prefetched").count,
        "queue_depth_high_water": int(high_water),
        "unit": "x",
    }


def run(rows_per_run: int = ROWS_PER_RUN, rtt_ms: float = STORE_RTT_MS, iters: int = 3):
    from paimon_tpu.fs.testing import LatencyFileIO
    from paimon_tpu.table import load_table

    rows = []
    tmp = tempfile.mkdtemp(prefix="paimon_tpu_pipe_")
    try:
        table = build_table(tmp, rows_per_run=rows_per_run)
        expect = rows_per_run * N_RUNS
        rows.append(run_profile(table, "local fs", expect, iters=iters))
        # same physical table behind the latency-injecting store
        LatencyFileIO.configure(read_ms=rtt_ms)
        try:
            slow = load_table(f"latency://{table.path}", commit_user="bench")
            rows.append(
                dict(
                    run_profile(slow, f"store rtt {rtt_ms:g} ms", expect, iters=iters),
                    rtt_ms=rtt_ms,
                )
            )
        finally:
            LatencyFileIO.configure()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return rows


def main():
    rows = run()
    for row in rows:
        row["cores"] = os.cpu_count()
        print(json.dumps(row))
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    with open(RESULTS, "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    from paimon_tpu.utils import enable_compile_cache

    enable_compile_cache()
    main()
