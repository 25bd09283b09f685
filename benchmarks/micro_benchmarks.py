#!/usr/bin/env python
"""Micro-benchmarks mirroring the reference suite (SURVEY.md §6 /
paimon-micro-benchmarks): table write throughput per format, full scans,
projected scans, merge-read with sorted runs. Prints one JSON line per
config. bench.py (repo root) remains the driver's single headline metric.

Usage: python benchmarks/micro_benchmarks.py [--rows N] [--fast]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paimon_tpu.utils import enable_compile_cache, require_device

enable_compile_cache()
PLATFORM, DEVICE_KIND, DEVICE_COUNT = require_device()

BASELINES = {
    # reference numbers from BASELINE.md (rows/s)
    "write.parquet": 64_800.0,
    "write.orc": 94_300.0,
    "write.avro": 74_400.0,
    "scan.parquet": 975_400.0,
    "scan.orc": 2_867_300.0,
    "scan.avro": 721_800.0,
    "scan.projected.orc": 4_187_400.0,  # the reference's projected number is ORC
    "merge-read.parquet": 975_400.0,
}


def make_table(tmp, fmt, rows, runs=1, write_only=False, merge_engine=None, extra_options=None, overlap=False):
    import paimon_tpu as pt
    from paimon_tpu.catalog import FileSystemCatalog

    cat = FileSystemCatalog(tmp, commit_user="bench")
    schema = pt.RowType.of(
        ("id", pt.BIGINT(False)),
        *[(f"c{i}", pt.BIGINT()) for i in range(6)],
        *[(f"d{i}", pt.DOUBLE()) for i in range(4)],
        *[(f"s{i}", pt.STRING()) for i in range(4)],
    )
    opts = {"bucket": "1", "file.format": fmt}
    if write_only:
        opts["write-only"] = "true"
    if merge_engine:
        opts["merge-engine"] = merge_engine
    opts.update(extra_options or {})
    name = f"bench.t_{fmt}_{runs}_{merge_engine or 'dedup'}"
    t = cat.create_table(name, schema, primary_keys=["id"], options=opts)
    rng = np.random.default_rng(7)
    per = rows // runs
    if overlap:
        # every run re-draws from the SAME key space: the merge truly
        # combines versions across all runs
        key_space = np.arange(per, dtype=np.int64)
    else:
        ids = rng.permutation(rows).astype(np.int64)
    elapsed = 0.0
    for r in range(runs):
        chunk = key_space if overlap else np.sort(ids[r * per : (r + 1) * per])
        data = {"id": chunk}
        for i in range(6):
            data[f"c{i}"] = chunk * (i + 1)
        for i in range(4):
            data[f"d{i}"] = chunk.astype(np.float64) + i
        for i in range(4):
            data[f"s{i}"] = np.array([f"v{i}-{int(x) % 997:04d}" for x in chunk], dtype=object)
        t0 = time.perf_counter()
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        w.write(data)
        wb.new_commit().commit(w.prepare_commit())
        elapsed += time.perf_counter() - t0
    return t, rows / elapsed


def bench_scan(t, rows, projection=None, iters=3, expect_rows=None):
    rb = t.new_read_builder()
    if projection:
        rb = rb.with_projection(projection)
    best = float("inf")
    for i in range(iters + 1):
        t0 = time.perf_counter()
        out = rb.new_read().read_all(rb.new_scan().plan())
        dt = time.perf_counter() - t0
        assert out.num_rows == (expect_rows if expect_rows is not None else rows)
        if i > 0:
            best = min(best, dt)
    return rows / best


def emit(metric, value, unit="rows/s"):
    base = BASELINES.get(metric)
    print(
        json.dumps(
            {
                "metric": metric,
                "value": round(value, 4 if value < 10 else 1),
                "unit": unit,
                "vs_baseline": round(value / base, 3) if base else None,
                "platform": PLATFORM, "device_kind": DEVICE_KIND, "devices": DEVICE_COUNT,
            }
        ),
        flush=True,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--fast", action="store_true", help="100k rows, parquet only")
    args = ap.parse_args()
    rows = 100_000 if args.fast else args.rows
    formats = ["parquet"] if args.fast else ["parquet", "orc", "avro"]
    for fmt in formats:
        tmp = tempfile.mkdtemp(prefix=f"ptb_{fmt}_")
        try:
            if fmt == "avro" and rows > 200_000:
                t, wtp = make_table(tmp, fmt, 200_000)  # row codec: keep runtime sane
                emit(f"write.{fmt}", wtp)
                emit(f"scan.{fmt}", bench_scan(t, 200_000, iters=1))
            else:
                t, wtp = make_table(tmp, fmt, rows)
                emit(f"write.{fmt}", wtp)
                emit(f"scan.{fmt}", bench_scan(t, rows))
                if fmt in ("parquet", "orc"):
                    emit(f"scan.projected.{fmt}", bench_scan(t, rows, projection=["id", "c0", "d0", "s0"]))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    # merge-read with 4 overlapping runs (the headline config, see bench.py),
    # then BASELINE.json headline #2 on the same table: full-compaction
    # throughput (GB/s of input rewritten through the merge kernel)
    tmp = tempfile.mkdtemp(prefix="ptb_mr_")
    try:
        t, _ = make_table(tmp, "parquet", rows, runs=4, write_only=True)
        emit("merge-read.parquet", bench_scan(t, rows))
        input_bytes = sum(f.file_size for f in t.store.restore_files((), 0))
        t2 = t.copy({"write-only": "false"})
        wb = t2.new_batch_write_builder()
        w = wb.new_write()
        t0 = time.perf_counter()
        w.compact(full=True)
        wb.new_commit().commit(w.prepare_commit())
        dt = time.perf_counter() - t0
        emit("full-compaction.gbps", input_bytes / dt / (1 << 30), unit="GB/s")
        emit("full-compaction.rows", rows / dt)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    # BASELINE.json configs 2-3: partial-update and aggregation merge engines
    # over overlapping runs (no published reference numbers -> vs_baseline null)
    for engine, extra in (
        ("partial-update", {}),
        ("aggregation", {"fields.c0.aggregate-function": "sum", "fields.d0.aggregate-function": "max"}),
    ):
        tmp = tempfile.mkdtemp(prefix="ptb_eng_")
        try:
            # 4 fully-overlapping runs: every key has 4 versions to combine
            t, _ = make_table(
                tmp, "parquet", rows, runs=4, write_only=True,
                merge_engine=engine, extra_options=extra, overlap=True,
            )
            emit(f"merge-read.{engine}", bench_scan(t, rows, expect_rows=rows // 4))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
