#!/usr/bin/env python
"""Benchmark: arrow vs native parquet ENCODE on the write path.

Headline: ingest throughput (rows/s) for a 1M-row flat primary-key table —
dictionary string key + numeric values, the merge pool-reuse shape — driven
through the real table write surface (new_batch_write_builder → write →
prepare_commit → commit), so the measured wall covers memtable, merge and
file encode exactly as production flushes do. Two identical tables differ
only in `format.parquet.encoder`.

No-regression guard: after the timed passes, EVERY natively-written data
file is read back with pyarrow (pq.read_table) and compared bit-identically
against the arrow-encoded table's merged view — a native file pyarrow
cannot read exactly is a benchmark failure, not a footnote.

Acceptance (ISSUE 5): native flush encode >= 1.2x arrow rows/s on this
shape. Results also land in benchmarks/results/encode_bench.json.
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

N_ROWS = 1_000_000
RESULTS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results", "encode_bench.json")


N_REGIONS = 256  # dictionary cardinality of the string key column


def build_data(n_rows):
    """Flat PK schema with a dictionary string key: PK = (region, id) where
    region is a low-cardinality string (the merge pool-reuse shape — its
    ranks become dictionary codes directly) and id makes rows unique.
    Rows arrive PK-sorted, the merged flush shape."""
    rng = np.random.default_rng(11)
    region_ids = np.sort(rng.integers(0, N_REGIONS, n_rows))
    regions = np.array([f"region-{int(x):04d}" for x in range(N_REGIONS)], dtype=object)
    perm = rng.permutation(n_rows).astype(np.int64)
    return {
        "region": regions[region_ids],
        "id": np.arange(n_rows, dtype=np.int64),
        "c1": perm * 3,
        "d1": perm.astype(np.float64) * 0.5,
        "tag": np.array([f"tag-{int(x) % 16}" for x in perm], dtype=object),
    }


def make_table(cat, name, encoder):
    import paimon_tpu as pt

    schema = pt.RowType.of(
        ("region", pt.STRING(False)),
        ("id", pt.BIGINT(False)),
        ("c1", pt.BIGINT()),
        ("d1", pt.DOUBLE()),
        ("tag", pt.STRING()),
    )
    return cat.create_table(
        f"bench.{name}",
        schema,
        primary_keys=["region", "id"],
        options={
            "bucket": "1",
            "file.format": "parquet",
            "write-only": "true",
            "format.parquet.encoder": encoder,
        },
    )


def ingest_once(table, data) -> float:
    t0 = time.perf_counter()
    wb = table.new_batch_write_builder()
    w = wb.new_write()
    w.write(data)
    wb.new_commit().commit(w.prepare_commit())
    return time.perf_counter() - t0


def run_headline(n_rows=N_ROWS, iters=3):
    """[ingest row, breakdown row] — the two bench.py write-path lines."""
    from paimon_tpu.catalog import FileSystemCatalog
    from paimon_tpu.metrics import encode_metrics

    data = build_data(n_rows)
    tmp = tempfile.mkdtemp(prefix="paimon_tpu_encode_bench_")
    try:
        cat = FileSystemCatalog(tmp, commit_user="bench")
        walls = {}
        for encoder in ("arrow", "native"):
            best = float("inf")
            for it in range(iters):
                table = make_table(cat, f"{encoder}{it}", encoder)
                g = encode_metrics()
                n0, f0 = g.counter("files_native").count, g.counter("files_fallback").count
                dt = ingest_once(table, data)
                best = min(best, dt)
                if encoder == "native":
                    assert g.counter("files_native").count > n0, "native encoder did not run"
                    assert g.counter("files_fallback").count == f0, "unexpected arrow fallback"
            walls[encoder] = best
        # ---- no-regression guard: pyarrow reads every native file exactly
        import pyarrow.parquet as pq

        arrow_t = make_table(cat, "guard_a", "arrow")
        native_t = make_table(cat, "guard_n", "native")
        ingest_once(arrow_t, data)
        ingest_once(native_t, data)
        rb_a, rb_n = arrow_t.new_read_builder(), native_t.new_read_builder()
        ref = rb_a.new_read().read_all(rb_a.new_scan().plan())
        native_files = []
        for root, _dirs, files in os.walk(tmp):
            if "guard_n" in root:
                native_files += [os.path.join(root, f) for f in files if f.endswith(".parquet") and "data-" in f]
        assert native_files, "no native data files found for the guard"
        pa_rows = 0
        for f in native_files:
            pa_rows += pq.read_table(f).num_rows
        assert pa_rows == n_rows, f"pyarrow read {pa_rows} rows from native files, expected {n_rows}"
        got = rb_n.new_read().read_all(rb_n.new_scan().plan())
        assert got.to_pydict() == ref.to_pydict(), "native-encoded table diverges from arrow-encoded"

        g = encode_metrics()
        ingest_row = {
            "metric": f"ingest throughput ({n_rows // 1_000_000 or 1}M-row PK write+flush, dict string key)",
            "arrow_rows_per_sec": round(n_rows / walls["arrow"], 1),
            "native_rows_per_sec": round(n_rows / walls["native"], 1),
            "native_vs_arrow": round(walls["arrow"] / walls["native"], 3),
            "unit": "rows/s",
        }
        breakdown_row = {
            "metric": "native encode breakdown (write path)",
            "pages_written": g.counter("pages_written").count,
            "bytes_written": g.counter("bytes_written").count,
            "dict_pages": g.counter("dict_pages").count,
            "files_native": g.counter("files_native").count,
            "files_fallback": g.counter("files_fallback").count,
            "encode_ms_mean": round(g.histogram("encode_ms").mean, 2),
            "stats_ms_mean": round(g.histogram("stats_ms").mean, 3),
            "unit": "counters",
        }
        return [ingest_row, breakdown_row]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main():
    rows = run_headline()
    for row in rows:
        print(json.dumps(row))
    ratio = rows[0]["native_vs_arrow"]
    verdict = {
        "metric": "native encode speedup target (>= 1.2x arrow)",
        "value": ratio,
        "pass": ratio >= 1.2,
        "unit": "x",
    }
    rows.append(verdict)
    print(json.dumps(verdict))
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    with open(RESULTS, "w") as f:
        json.dump({"rows": N_ROWS, "results": rows}, f, indent=1)
    print(json.dumps({"metric": "encode_bench results file", "value": RESULTS}))


if __name__ == "__main__":
    from paimon_tpu.utils import enable_compile_cache

    enable_compile_cache()
    main()
