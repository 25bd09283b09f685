#!/usr/bin/env python
"""Benchmark: cold-vs-warm repeated scan (plan + read_all) through the
byte-budget caches (utils.cache).

Workload: a primary-key table written as several sorted runs then fully
compacted (the steady state of a serving table), re-scanned repeatedly —
the repeated-query shape the manifest object cache and decoded data-file
cache exist for. "Cold" clears both caches first (every plan re-fetches the
snapshot + manifests and re-decodes every parquet file); "warm" re-runs the
identical plan + read against populated caches.

Prints one JSON line per metric:
  repeated-scan cold  (ms)
  repeated-scan warm  (ms)
  repeated-scan speedup (warm cache)   <- acceptance: >= 5x
plus a final line with the cache counters from the metrics registry.
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

N_ROWS = 400_000
N_RUNS = 4


def build_table(path: str):
    import paimon_tpu as pt
    from paimon_tpu.catalog import FileSystemCatalog

    cat = FileSystemCatalog(path, commit_user="bench")
    schema = pt.RowType.of(
        ("id", pt.BIGINT(False)),
        ("c1", pt.BIGINT()),
        ("d1", pt.DOUBLE()),
        ("s1", pt.STRING()),
        ("s2", pt.STRING()),
    )
    table = cat.create_table(
        "bench.scan_cache",
        schema,
        primary_keys=["id"],
        options={
            "bucket": "1",
            "file.format": "parquet",
            "cache.manifest.max-memory-size": "256 mb",
            "cache.data-file.max-memory-size": "1 gb",
        },
    )
    rng = np.random.default_rng(11)
    ids = rng.permutation(N_ROWS).astype(np.int64)
    per = N_ROWS // N_RUNS
    for r in range(N_RUNS):
        chunk = np.sort(ids[r * per : (r + 1) * per])
        wb = table.new_batch_write_builder()
        w = wb.new_write()
        w.write(
            {
                "id": chunk,
                "c1": chunk * 3,
                "d1": chunk.astype(np.float64) * 0.5,
                "s1": np.array([f"val-{int(x) % 1000:04d}" for x in chunk], dtype=object),
                "s2": np.array([f"tag-{int(x) % 10}" for x in chunk], dtype=object),
            }
        )
        if r == N_RUNS - 1:
            w.compact(full=True)  # settle into one sorted run (serving shape)
        wb.new_commit().commit(w.prepare_commit())
    return table


def scan_once(table) -> float:
    rb = table.new_read_builder()
    t0 = time.perf_counter()
    splits = rb.new_scan().plan()
    out = rb.new_read().read_all(splits)
    dt = (time.perf_counter() - t0) * 1000
    assert out.num_rows == N_ROWS, out.num_rows
    return dt


def main():
    from paimon_tpu.metrics import registry
    from paimon_tpu.utils import cache as cache_mod

    tmp = tempfile.mkdtemp(prefix="paimon_tpu_scan_cache_")
    try:
        table = build_table(tmp)
        # warm jit / pyarrow process globals WITHOUT the caches, so cold-vs-
        # warm isolates the caching effect rather than first-run compile cost
        plain = table.copy(
            {"cache.manifest.max-memory-size": "0 b", "cache.data-file.max-memory-size": "0 b"}
        )
        scan_once(plain)

        cold = min(self_time for self_time in (_cold_pass(table, cache_mod) for _ in range(3)))
        scan_once(table)  # populate
        warm = min(scan_once(table) for _ in range(5))
        speedup = cold / warm if warm > 0 else float("inf")
        print(json.dumps({"metric": "repeated-scan cold", "value": round(cold, 2), "unit": "ms"}))
        print(json.dumps({"metric": "repeated-scan warm", "value": round(warm, 2), "unit": "ms"}))
        print(
            json.dumps(
                {
                    "metric": "repeated-scan speedup (warm cache)",
                    "value": round(speedup, 2),
                    "unit": "x",
                    "target": ">= 5x",
                    "rows": N_ROWS,
                }
            )
        )
        counters = {
            name: stats
            for name, stats in registry.snapshot().items()
            if name.startswith("cache")
        }
        print(json.dumps({"metric": "cache counters", "value": counters}))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _cold_pass(table, cache_mod) -> float:
    cache_mod.clear_all()
    return scan_once(table)


if __name__ == "__main__":
    from paimon_tpu.utils import enable_compile_cache

    enable_compile_cache()
    main()
