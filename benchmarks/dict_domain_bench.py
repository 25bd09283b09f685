#!/usr/bin/env python
"""Benchmark: compressed-domain merge & compaction (ISSUE 10,
merge.dict-domain) — dictionary codes as the merge currency end-to-end.

Three schemas spanning the dictionary decision space:

  dict_heavy — composite (BIGINT, STRING) key + four low-cardinality STRING
               payload columns: decode, key lanes, dedup winners, stats and
               the output dictionary pages all stay in the code domain
  mixed      — BIGINT key, two STRING + two numeric payload columns
  non_dict   — BIGINT key, numeric payload only: the code domain never
               engages; the row is the no-regression guard

Per schema x workload (merge-read, compaction rewrite, sort-compact) the
bench measures rows/s with merge.dict-domain ON vs OFF through the NATIVE
decoder+encoder (the current native path is the baseline the >=2x headline
is against). EVERY timed pass first asserts the code-domain output
byte-identical to the expanded-domain oracle, and the compaction passes
additionally re-read every output data file with plain pyarrow
(pq.read_table) — an independent reader must see identical rows.

Acceptance (ISSUE 10): compaction rewrite rows/s >= 2x on dict_heavy.
Results land in benchmarks/results/dict_domain_bench.json.
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
ITERS = 3
RESULTS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results", "dict_domain_bench.json")


def _schemas():
    import paimon_tpu as pt

    return {
        "dict_heavy": dict(
            schema=pt.RowType.of(
                ("k", pt.BIGINT(False)),
                ("cat", pt.STRING(False)),
                ("s1", pt.STRING()),
                ("s2", pt.STRING()),
                ("s3", pt.STRING()),
                ("s4", pt.STRING()),
            ),
            keys=["k", "cat"],
            sort_cols=["cat", "s1"],
        ),
        "mixed": dict(
            schema=pt.RowType.of(
                ("k", pt.BIGINT(False)),
                ("s1", pt.STRING()),
                ("s2", pt.STRING()),
                ("v1", pt.BIGINT()),
                ("v2", pt.DOUBLE()),
            ),
            keys=["k"],
            sort_cols=["s1", "v1"],
        ),
        "non_dict": dict(
            schema=pt.RowType.of(
                ("k", pt.BIGINT(False)), ("v1", pt.BIGINT()), ("v2", pt.DOUBLE())
            ),
            keys=["k"],
            sort_cols=["v1"],
        ),
    }


def _rows(kind, n, rng):
    k = rng.integers(0, n * 2, n).astype(np.int64)
    if kind == "dict_heavy":
        return {
            "k": k,
            "cat": np.array([f"category-{int(x):03d}" for x in rng.integers(0, 200, n)], dtype=object),
            "s1": np.array([f"city-{int(x):04d}" for x in rng.integers(0, 800, n)], dtype=object),
            "s2": np.array([f"status-{int(x):02d}" for x in rng.integers(0, 12, n)], dtype=object),
            "s3": np.array([f"device-{int(x):03d}" for x in rng.integers(0, 300, n)], dtype=object),
            "s4": np.array([f"plan-{int(x):02d}" for x in rng.integers(0, 40, n)], dtype=object),
        }
    if kind == "mixed":
        return {
            "k": k,
            "s1": np.array([f"region-{int(x):03d}" for x in rng.integers(0, 100, n)], dtype=object),
            "s2": np.array([f"tag-{int(x):02d}" for x in rng.integers(0, 30, n)], dtype=object),
            "v1": rng.integers(0, 1 << 40, n).astype(np.int64),
            "v2": rng.random(n),
        }
    if kind == "non_dict":
        return {"k": k, "v1": rng.integers(0, 1 << 40, n).astype(np.int64), "v2": rng.random(n)}
    raise AssertionError(kind)


def _base_opts(dd, extra=None):
    opts = {
        "bucket": "1",
        "file.format": "parquet",
        "format.parquet.decoder": "native",
        "format.parquet.encoder": "native",
        "cache.data-file.max-memory-size": "0 b",
        "merge.dict-domain": "true" if dd else "false",
    }
    opts.update(extra or {})
    return opts


def _write_runs(table, kind, n, runs, seed=7):
    rng = np.random.default_rng(seed)
    per = n // runs
    for _ in range(runs):
        wb = table.new_batch_write_builder()
        w = wb.new_write()
        w.write(_rows(kind, per, rng))
        wb.new_commit().commit(w.prepare_commit())


def _dict_counters():
    from paimon_tpu.metrics import dict_metrics

    g = dict_metrics()
    return {
        k: g.counter(k).count
        for k in ("pools_unified", "codes_remapped", "rows_code_domain", "fallback_expanded")
    }


def _pyarrow_state(table, warehouse, name):
    """Every data file of the table's current snapshot read back through
    plain pyarrow — the independent-reader guard."""
    import pyarrow.parquet as pq

    by_name = {}
    for root, _dirs, fnames in os.walk(warehouse):
        if f"/{name}" in root or root.endswith(name):
            by_name.update({f: os.path.join(root, f) for f in fnames if f.startswith("data-")})
    rows = []
    rb = table.new_read_builder()
    for s in rb.new_scan().plan():  # plan order, the order the reader sees
        for f in s.files:
            rows.extend(pq.read_table(by_name[f.file_name]).to_pylist())
    assert rows, f"pyarrow readback found no live data files for {name}"
    return rows


def bench_merge_read(cat_path, kind, spec):
    """Same physical table, table.copy flips only merge.dict-domain: the
    delta is decode + key ranks + winner gathers in the code domain. The
    timed region includes a to_arrow conversion — both modes must DELIVER
    the rows, the code domain as dictionary arrays."""
    from paimon_tpu.catalog import FileSystemCatalog

    cat = FileSystemCatalog(cat_path, commit_user="dict-bench")
    row = {"schema": kind, "workload": "merge_read", "rows": N_ROWS}
    base = cat.create_table(
        f"b.mr_{kind}", spec["schema"], primary_keys=spec["keys"],
        options=_base_opts(False, {"write-only": "true"}),
    )
    _write_runs(base, kind, N_ROWS, N_RUNS)
    outs = {}
    for dd in (False, True):
        t = base.copy({"merge.dict-domain": "true" if dd else "false"})
        rb = t.new_read_builder()
        best = float("inf")
        c0 = _dict_counters()
        out = None
        for it in range(ITERS + 1):  # first pass warms jit caches
            t0 = time.perf_counter()
            out = rb.new_read().read_all(rb.new_scan().plan())
            out.to_arrow()  # delivery included (code domain hands dictionaries)
            dt = time.perf_counter() - t0
            if it > 0:
                best = min(best, dt)
        outs[dd] = out
        tag = "on" if dd else "off"
        row[f"rows_per_sec_{tag}"] = round(out.num_rows / best, 1)
        if dd:
            row["counters"] = {k: v - c0[k] for k, v in _dict_counters().items()}
    assert outs[True].to_pylist() == outs[False].to_pylist(), f"{kind}: code-domain read differs"
    row["speedup"] = round(row["rows_per_sec_on"] / row["rows_per_sec_off"], 3)
    return row


def bench_compaction(cat_path, kind, spec):
    """The headline: full compaction rewrite (read -> merge -> encode) of
    N_RUNS overlapping sorted runs, fresh table per (option, attempt).
    Before timing counts, the ON table's compacted state is asserted equal
    to the OFF table's through the expanded reader AND through pyarrow."""
    from paimon_tpu.catalog import FileSystemCatalog

    cat = FileSystemCatalog(cat_path, commit_user="dict-bench")
    n = N_ROWS
    row = {"schema": kind, "workload": "compaction_rewrite", "rows": n}
    states = {}
    pa_states = {}
    for dd in (False, True):
        best = float("inf")
        for attempt in range(ITERS):
            name = f"cp_{kind}_{int(dd)}_{attempt}"
            t = cat.create_table(
                f"b.{name}", spec["schema"], primary_keys=spec["keys"],
                options=_base_opts(dd),  # compaction enabled (manual trigger)
            )
            _write_runs(t, kind, n, N_RUNS)
            wb = t.new_batch_write_builder()
            w = wb.new_write()
            t0 = time.perf_counter()
            w.compact(full=True)
            best = min(best, time.perf_counter() - t0)
            wb.new_commit().commit(w.prepare_commit())
            if attempt == 0:
                # oracle check through the EXPANDED reader (option off) so
                # both states are compared by one decode path
                plain = t.copy({"merge.dict-domain": "false"})
                rb = plain.new_read_builder()
                states[dd] = rb.new_read().read_all(rb.new_scan().plan()).to_pylist()
                pa_states[dd] = _pyarrow_state(t, cat_path, name)
        row[f"rows_per_sec_{'on' if dd else 'off'}"] = round(n / best, 1)
    assert states[True] == states[False], f"{kind}: compacted state differs"
    assert pa_states[True] == pa_states[False], f"{kind}: pyarrow readback differs"
    row["speedup"] = round(row["rows_per_sec_on"] / row["rows_per_sec_off"], 3)
    return row


def bench_sort_compact(cat_path, kind, spec):
    from paimon_tpu.catalog import FileSystemCatalog
    from paimon_tpu.table.sort_compact import sort_compact

    cat = FileSystemCatalog(cat_path, commit_user="dict-bench")
    n = N_ROWS // 2
    row = {"schema": kind, "workload": "sort_compact", "rows": n}
    views = {}
    for dd in (False, True):
        best = float("inf")
        for attempt in range(2):
            t = cat.create_table(
                f"b.sc_{kind}_{int(dd)}_{attempt}", spec["schema"],
                options=_base_opts(dd),
            )
            _write_runs(t, kind, n, 2)
            t0 = time.perf_counter()
            total = sort_compact(t, spec["sort_cols"], order="order")
            best = min(best, time.perf_counter() - t0)
            rb = t.new_read_builder()
            views[dd] = rb.new_read().read_all(rb.new_scan().plan()).to_pylist()
        row[f"rows_per_sec_{'on' if dd else 'off'}"] = round(total / best, 1)
    assert views[True] == views[False], f"{kind}: clustered view differs"
    row["speedup"] = round(row["rows_per_sec_on"] / row["rows_per_sec_off"], 3)
    return row


def run(write_results=True):
    assert os.environ.get("PAIMON_TPU_DICT_DOMAIN") is None, (
        "unset PAIMON_TPU_DICT_DOMAIN: the bench flips the table option"
    )
    tmp = tempfile.mkdtemp(prefix="paimon_tpu_dict_bench_")
    rows = []
    try:
        for kind, spec in _schemas().items():
            rows.append(bench_merge_read(os.path.join(tmp, f"mr_{kind}"), kind, spec))
            rows.append(bench_compaction(os.path.join(tmp, f"cp_{kind}"), kind, spec))
            rows.append(bench_sort_compact(os.path.join(tmp, f"sc_{kind}"), kind, spec))
            for r in rows[-3:]:
                print(json.dumps(r))
        headline = next(
            r for r in rows if r["schema"] == "dict_heavy" and r["workload"] == "compaction_rewrite"
        )
        summary = {
            "metric": "compaction rewrite dict-domain on vs off (dict_heavy)",
            "speedup": headline["speedup"],
            "target": 2.0,
            "pass": headline["speedup"] >= 2.0,
        }
        print(json.dumps(summary))
        if write_results:
            os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
            with open(RESULTS, "w") as f:
                json.dump({"rows": rows, "summary": summary}, f, indent=1)
        return rows, summary
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    from paimon_tpu.utils import enable_compile_cache

    enable_compile_cache()
    run()
