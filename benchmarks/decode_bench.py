#!/usr/bin/env python
"""Benchmark: arrow vs native vs native+pushdown parquet decode, per encoding.

One file per (encoding, compression) config — plain / dictionary / delta
columns under uncompressed / snappy / zstd — read three ways through the
same `ParquetFormat.read` surface:

  arrow            pyarrow C++ decode (the default backend)
  native           paimon_tpu.decode page decode, full expansion
  native+pushdown  same, with a selective dictionary equality predicate:
                   the compressed-domain gate expands only surviving pages

Prints one JSON line per (config, backend) with rows/s, plus a pushdown
line quantifying pages decoded vs skipped (acceptance: the pushdown pass
expands strictly fewer pages than full decode). The result table is also
written to benchmarks/results/decode_bench.json next to the other round
artifacts.
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

N_ROWS = 300_000
N_TAGS = 16  # dictionary cardinality; clustered so pages are homogeneous
RESULTS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results", "decode_bench.json")

CONFIGS = [
    # (name, dictionary, delta, compression)
    ("plain", "false", False, "none"),
    ("plain-snappy", "false", False, "snappy"),
    ("plain-zstd", "false", False, "zstd"),
    ("dict", "true", False, "none"),
    ("dict-snappy", "true", False, "snappy"),
    ("dict-zstd", "true", False, "zstd"),
    ("delta-zstd", None, True, "zstd"),
]


def build_batch():
    import paimon_tpu as pt
    from paimon_tpu.data.batch import ColumnBatch

    schema = pt.RowType.of(
        ("id", pt.BIGINT(False)),
        ("v", pt.DOUBLE()),
        ("tag", pt.STRING()),
        ("seq", pt.BIGINT()),
    )
    rng = np.random.default_rng(23)
    tag = np.sort(rng.integers(0, N_TAGS, N_ROWS))  # clustered dict column
    data = {
        "id": [int(x) for x in np.arange(N_ROWS)],
        "v": [float(x) for x in rng.random(N_ROWS)],
        "tag": [f"tag-{int(t):02d}" for t in tag],
        "seq": [int(x) for x in np.cumsum(rng.integers(0, 9, N_ROWS))],  # delta-friendly
    }
    return schema, ColumnBatch.from_pydict(schema, data)


def write_config(tmp, schema, batch, name, dictionary, delta, compression):
    from paimon_tpu.format.parquet import ParquetFormat
    from paimon_tpu.fs import LocalFileIO

    path = os.path.join(tmp, f"{name}.parquet")
    if delta:
        # pyarrow-only write path: per-column DELTA_BINARY_PACKED
        import pyarrow.parquet as pq

        pq.write_table(
            batch.to_arrow(),
            path,
            compression=compression if compression != "none" else "NONE",
            use_dictionary=False,
            column_encoding={"id": "DELTA_BINARY_PACKED", "seq": "DELTA_BINARY_PACKED",
                             "v": "PLAIN", "tag": "PLAIN"},
            data_page_size=64 << 10,
        )
    else:
        ParquetFormat().write(
            LocalFileIO(),
            path,
            batch,
            compression=compression,
            format_options={
                "parquet.enable.dictionary": dictionary,
                "parquet.page-size": str(64 << 10),
            },
        )
    return path


def read_once(path, schema, decoder, predicate=None) -> tuple[float, int]:
    from paimon_tpu.data.batch import concat_batches
    from paimon_tpu.format.parquet import ParquetFormat
    from paimon_tpu.fs import LocalFileIO

    t0 = time.perf_counter()
    parts = list(ParquetFormat(decoder=decoder).read(LocalFileIO(), path, schema, predicate=predicate))
    out = concat_batches(parts)
    # touch every lazy string column so arrow's deferred materialization is
    # included in the measured decode (the native path materializes eagerly)
    for name in out.schema.field_names:
        _ = out.column(name).values
    return time.perf_counter() - t0, out.num_rows


def bench(path, schema, decoder, predicate=None, iters=3) -> tuple[float, int]:
    best, rows = float("inf"), 0
    read_once(path, schema, decoder, predicate)  # warm (codecs, jit, page cache)
    for _ in range(iters):
        dt, rows = read_once(path, schema, decoder, predicate)
        best = min(best, dt)
    return best, rows


def main():
    from paimon_tpu.data import predicate as P
    from paimon_tpu.metrics import decode_metrics

    tmp = tempfile.mkdtemp(prefix="paimon_tpu_decode_bench_")
    rows_out = []
    try:
        schema, batch = build_batch()
        pred = P.equal("tag", f"tag-{N_TAGS // 2:02d}")  # ~1/N_TAGS of rows survive
        for name, dictionary, delta, compression in CONFIGS:
            path = write_config(tmp, schema, batch, name, dictionary, delta, compression)
            for decoder in ("arrow", "native"):
                dt, n = bench(path, schema, decoder)
                assert n == N_ROWS, (name, decoder, n)
                row = {
                    "metric": f"decode {name} [{decoder}]",
                    "value": round(N_ROWS / dt, 1),
                    "unit": "rows/s",
                }
                rows_out.append(row)
                print(json.dumps(row))
            if dictionary == "true":
                g = decode_metrics()
                d0, s0 = g.counter("pages_decoded").count, g.counter("pages_skipped").count
                dt, n = bench(path, schema, "native", predicate=pred, iters=1)
                decoded = g.counter("pages_decoded").count - d0
                skipped = g.counter("pages_skipped").count - s0
                assert skipped > 0 and decoded < decoded + skipped, (
                    "pushdown must expand strictly fewer pages than full decode"
                )
                row = {
                    "metric": f"decode {name} [native+pushdown, selective eq]",
                    "value": round(N_ROWS / dt, 1),
                    "unit": "rows/s (input rows over wall)",
                    "surviving_rows": n,
                    "pages_expanded": decoded,
                    "pages_skipped": skipped,
                }
                rows_out.append(row)
                print(json.dumps(row))
        os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
        with open(RESULTS, "w") as f:
            json.dump({"rows": N_ROWS, "results": rows_out}, f, indent=1)
        print(json.dumps({"metric": "decode_bench results file", "value": RESULTS}))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    from paimon_tpu.utils import enable_compile_cache

    enable_compile_cache()
    main()
