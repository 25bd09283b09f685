#!/usr/bin/env python
"""BASELINE.json configs 2-5, runnable at scaled sizes.

  #2  partial-update merge-read, 4 sorted runs, predicate pushdown on 2 int
      columns (full scale 10M rows)
  #3  aggregation (sum/max) over 8 buckets data-parallel, ORC
      (full scale 50M rows)
  #4  streaming CDC upsert -> universal compaction (full scale 100M)
  #5  batch full-compaction of a many-bucket table + z-order clustering
      (full scale 1B / 64 buckets)

Default sizes fit CI; --scale N multiplies row counts (1.0 ~ a few million
total). Each config prints one JSON line; vs_baseline uses the reference's
975.4 Krows/s single-thread parquet scan where a denominator makes sense.
Run with JAX_PLATFORMS=cpu for the virtual mesh or on the real chip.

Usage: python benchmarks/baseline_configs.py [--scale N] [--configs 2,3,4,5]
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

BASE = 975_400.0


def emit(metric, value, unit="rows/s", vs=None, **extra):
    print(
        json.dumps(
            {"metric": metric, "value": round(value, 1), "unit": unit,
             "vs_baseline": round(value / BASE, 3) if vs is None else vs,
             "platform": PLATFORM, "device_kind": DEVICE_KIND, "devices": DEVICE_COUNT, **extra}
        ),
        flush=True,
    )


def _mk(tmp, name, schema, pk, options):
    import paimon_tpu as pt
    from paimon_tpu.catalog import FileSystemCatalog

    cat = FileSystemCatalog(tmp, commit_user="bench")
    return cat.create_table(name, schema, primary_keys=pk, options=options)


def config2(scale: float):
    """10M-row partial-update, 4 overlapping runs, 2-int-col predicate."""
    import paimon_tpu as pt
    from paimon_tpu.data.predicate import and_, greater_or_equal, less_than

    rows = int(2_000_000 * scale)
    tmp = tempfile.mkdtemp(prefix="bc2_")
    try:
        schema = pt.RowType.of(
            ("id", pt.BIGINT(False)), ("a", pt.BIGINT()), ("b", pt.BIGINT()),
            ("d0", pt.DOUBLE()), ("d1", pt.DOUBLE()), ("s0", pt.STRING()),
        )
        t = _mk(tmp, "db.c2", schema, ["id"], {"bucket": "1", "merge-engine": "partial-update", "write-only": "true"})
        per = rows // 4
        ids = np.arange(per, dtype=np.int64)
        for r in range(4):
            wb = t.new_batch_write_builder()
            w = wb.new_write()
            w.write({
                "id": ids,
                "a": ids % 1000 if r % 2 == 0 else [None] * per,
                "b": [None] * per if r % 2 == 0 else ids % 777,
                "d0": ids * 0.5 + r,
                "d1": [None] * per if r < 2 else ids * 1.5,
                "s0": np.array([f"v{int(x) % 97}" for x in ids], dtype=object),
            })
            wb.new_commit().commit(w.prepare_commit())
        pred = and_(greater_or_equal("a", 100), less_than("b", 500))
        rb = t.new_read_builder().with_filter(pred)
        best = float("inf")
        for it in range(3):
            t0 = time.perf_counter()
            out = rb.new_read().read_all(rb.new_scan().plan())
            dt = time.perf_counter() - t0
            if it:
                best = min(best, dt)
        emit("config2.partial-update.predicates", rows / best, rows=rows, matched=out.num_rows)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def config3(scale: float):
    """Aggregation (sum/max) over 8 buckets, ORC, mesh-parallel read."""
    import paimon_tpu as pt

    rows = int(4_000_000 * scale)
    tmp = tempfile.mkdtemp(prefix="bc3_")
    try:
        schema = pt.RowType.of(
            ("id", pt.BIGINT(False)), ("sum_col", pt.BIGINT()), ("max_col", pt.DOUBLE())
        )
        import jax

        mesh_ok = len(jax.devices()) >= 8
        t = _mk(tmp, "db.c3", schema, ["id"], {
            "bucket": "8", "file.format": "orc", "merge-engine": "aggregation",
            "fields.sum_col.aggregate-function": "sum",
            "fields.max_col.aggregate-function": "max",
            "write-only": "true",
            **({"merge.engine": "mesh"} if mesh_ok else {}),
        })
        per = rows // 4
        rng = np.random.default_rng(1)
        for r in range(4):
            ids = rng.integers(0, rows // 8, per)
            wb = t.new_batch_write_builder()
            w = wb.new_write()
            w.write({"id": ids, "sum_col": ids % 7, "max_col": ids * 0.25})
            wb.new_commit().commit(w.prepare_commit())
        rb = t.new_read_builder()
        best = float("inf")
        for it in range(3):
            t0 = time.perf_counter()
            out = rb.new_read().read_all(rb.new_scan().plan())
            dt = time.perf_counter() - t0
            if it:
                best = min(best, dt)
        emit("config3.aggregation.orc.8buckets", rows / best, rows=rows, keys=out.num_rows, mesh=mesh_ok)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def config4(scale: float):
    """Streaming CDC upsert with periodic universal compaction."""
    import paimon_tpu as pt

    rows = int(1_000_000 * scale)
    tmp = tempfile.mkdtemp(prefix="bc4_")
    try:
        schema = pt.RowType.of(("id", pt.BIGINT(False)), ("v", pt.DOUBLE()), ("tag", pt.STRING()))
        t = _mk(tmp, "db.c4", schema, ["id"], {"bucket": "1", "num-sorted-run.compaction-trigger": "4"})
        wb = t.new_stream_write_builder()
        w = wb.new_write()
        c = wb.new_commit()
        rng = np.random.default_rng(2)
        batches = 20
        per = rows // batches
        t0 = time.perf_counter()
        for b in range(batches):
            ids = rng.integers(0, rows // 2, per)
            w.write({"id": ids, "v": ids * 0.5 + b, "tag": np.array([f"t{b}"] * per, dtype=object)})
            c.commit_messages(b + 1, w.prepare_commit())
        dt = time.perf_counter() - t0
        # denominator: the reference's parquet WRITE baseline (64.8 Krows/s,
        # TableWriterBenchmark) — this is a write workload
        emit("config4.streaming-upsert.compacting", rows / dt, rows=rows, commits=batches,
             vs=round(rows / dt / 64_800.0, 3))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def config5(scale: float):
    """Full compaction of a many-bucket table, then z-order clustering."""
    import paimon_tpu as pt
    from paimon_tpu.table.compactor import DedicatedCompactor
    from paimon_tpu.table.sort_compact import sort_compact

    rows = int(2_000_000 * scale)
    buckets = 16
    tmp = tempfile.mkdtemp(prefix="bc5_")
    try:
        import jax

        mesh_ok = len(jax.devices()) >= 8
        schema = pt.RowType.of(("id", pt.BIGINT(False)), ("x", pt.BIGINT()), ("y", pt.BIGINT()), ("v", pt.DOUBLE()))
        t = _mk(tmp, "db.c5", schema, ["id"], {
            "bucket": str(buckets), "write-only": "true",
            **({"merge.engine": "mesh"} if mesh_ok else {}),
        })
        rng = np.random.default_rng(3)
        per = rows // 4
        for r in range(4):
            ids = rng.integers(0, rows, per)
            wb = t.new_batch_write_builder()
            w = wb.new_write()
            w.write({"id": ids, "x": ids % 4096, "y": (ids * 7) % 4096, "v": ids * 1.0})
            wb.new_commit().commit(w.prepare_commit())
        input_bytes = sum(e.file.file_size for e in t.store.new_scan().plan().entries)
        t0 = time.perf_counter()
        assert DedicatedCompactor(t).run_once(full=True)
        dt = time.perf_counter() - t0
        emit("config5.full-compaction.16buckets", rows / dt, rows=rows,
             gb_per_s=round(input_bytes / dt / (1 << 30), 3), mesh=mesh_ok, vs=None)
        # z-order clustering on an append clone of the data
        ta = _mk(tmp, "db.c5z", schema, [], {"bucket": "1"})
        wb = ta.new_batch_write_builder()
        w = wb.new_write()
        ids = rng.integers(0, rows, min(rows, 500_000))
        w.write({"id": ids, "x": ids % 4096, "y": (ids * 7) % 4096, "v": ids * 1.0})
        wb.new_commit().commit(w.prepare_commit())
        t0 = time.perf_counter()
        n = sort_compact(ta, ["x", "y"], order="zorder")
        dt = time.perf_counter() - t0
        emit("config5.zorder-cluster", n / dt, rows=n, vs=None)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--configs", default="2,3,4,5")
    args = ap.parse_args()
    fns = {"2": config2, "3": config3, "4": config4, "5": config5}
    for c in args.configs.split(","):
        fns[c.strip()](args.scale)


if __name__ == "__main__":
    main()
