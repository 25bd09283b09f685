#!/usr/bin/env python
"""Benchmark: device-side skew-aware joins (ISSUE 12, ops/join).

A star-schema fact x dimension equi-join — 1M fact rows against a 100k-row
dimension on a STRING customer key — at three probe-key skew levels:

  uniform — cust drawn uniformly over the dimension's 100k keys
  zipf    — a heavy-tailed (Pareto) draw: popular customers dominate
  hot50   — ONE customer holds 50% of the fact rows (the JSPIM adversary)

Both sides are REAL tables read through the native decoder with
merge.dict-domain on, so the join keys arrive as code-backed columns and
the kernel matches on unified dictionary codes with zero string
materialization (join{code_domain_joins} in the breakdown).

Per skew level the bench measures the device join (ops/join.join_batches,
auto engine + auto partitioning with the skew split) against the host
row-at-a-time baseline — the python dict probe loop every lookup-join ran
before this subsystem (one .get per fact row). EVERY timed pass first
asserts the device pairs bit-identical to the host loop's pairs.

Acceptance (ISSUE 12): device >= 5x the host loop on the 1M x 100k join,
and hot50 wall <= 2x uniform wall (the skew split working). Results land
in benchmarks/results/join_bench.json.
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

N_FACT = 1_000_000
N_DIM = 100_000
ITERS = 3
RESULTS = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "results", "join_bench.json"
)


def _skew_keys(rng, n, dom):
    return {
        "uniform": rng.integers(0, dom, n),
        "zipf": np.minimum((rng.pareto(1.1, n) * dom / 20).astype(np.int64), dom - 1),
        "hot50": np.where(rng.random(n) < 0.5, 4242, rng.integers(0, dom, n)),
    }


def build_tables(tmp):
    import paimon_tpu as pt
    from paimon_tpu.catalog import FileSystemCatalog

    cat = FileSystemCatalog(tmp, commit_user="join-bench")
    dim = cat.create_table(
        "bench.dim",
        pt.RowType.of(
            ("cid", pt.STRING(False)), ("name", pt.STRING()), ("rate", pt.DOUBLE())
        ),
        primary_keys=["cid"],
        options={"bucket": "1", "write-only": "true", "format.parquet.encoder": "native"},
    )
    rng = np.random.default_rng(12)
    wb = dim.new_batch_write_builder()
    w = wb.new_write()
    w.write({
        "cid": np.array([f"C{i:06d}" for i in range(N_DIM)], dtype=object),
        "name": np.array([f"customer-{i}" for i in range(N_DIM)], dtype=object),
        "rate": rng.random(N_DIM),
    })
    wb.new_commit().commit(w.prepare_commit())

    fields = [("id", pt.BIGINT(False))]
    fields += [(f"cust_{s}", pt.STRING(False)) for s in ("uniform", "zipf", "hot50")]
    fields += [("amount", pt.DOUBLE()), ("qty", pt.BIGINT())]
    fact = cat.create_table(
        "bench.fact",
        pt.RowType.of(*fields),
        primary_keys=["id"],
        options={"bucket": "1", "write-only": "true", "format.parquet.encoder": "native"},
    )
    keys = _skew_keys(rng, N_FACT, N_DIM)
    per = N_FACT // 4
    for r in range(4):
        sl = slice(r * per, (r + 1) * per)
        wb = fact.new_batch_write_builder()
        w = wb.new_write()
        data = {
            "id": np.arange(sl.start, sl.stop, dtype=np.int64),
            "amount": rng.random(per).round(4),
            "qty": rng.integers(1, 9, per),
        }
        for s, k in keys.items():
            data[f"cust_{s}"] = np.array(
                [f"C{int(x):06d}" for x in k[sl]], dtype=object
            )
        w.write(data)
        wb.new_commit().commit(w.prepare_commit())
    return fact, dim


def _read(table):
    t = table.copy({
        "merge.dict-domain": "true",
        "format.parquet.decoder": "native",
        "cache.data-file.max-memory-size": "0 b",
    })
    rb = t.new_read_builder()
    return rb.new_read().read_all(rb.new_scan().plan())


def host_row_at_a_time(cust_values, dim_cids):
    """The pre-ISSUE-12 lookup path: a python dict keyed by the join key,
    probed one fact row at a time."""
    pos: dict = {}
    for j, c in enumerate(dim_cids):
        pos.setdefault(c, []).append(j)
    wall = float("inf")
    for _ in range(2):  # best of two: same fairness as the device side
        lt, rt = [], []
        t0 = time.perf_counter()
        for i, c in enumerate(cust_values):
            for j in pos.get(c, ()):
                lt.append(i)
                rt.append(j)
        wall = min(wall, time.perf_counter() - t0)
    return np.asarray(lt, dtype=np.int64), np.asarray(rt, dtype=np.int64), wall


def run(fact_batch, dim_batch, skews=("uniform", "zipf", "hot50")):
    from paimon_tpu.metrics import join_metrics, registry
    from paimon_tpu.ops.join import join_batches

    registry.reset()
    dim_cids = dim_batch.column("cid").to_pylist()
    rows = []
    walls = {}
    # the 4-partition hot50 pass exercises the JSPIM skew split (one key =
    # 50% of probes, dealt round-robin across every partition) — output
    # still asserted identical to the host loop
    passes = [(s, None) for s in skews] + [("hot50", {"join.partitions": "4"})]
    for skew, opts in passes:
        key = f"cust_{skew}"
        cust = fact_batch.column(key).to_pylist()
        olt, ort, host_wall = host_row_at_a_time(cust, dim_cids)
        best = float("inf")
        for _ in range(ITERS):
            t0 = time.perf_counter()
            res = join_batches(
                fact_batch, dim_batch, [key], ["cid"], how="inner", options=opts
            )
            best = min(best, time.perf_counter() - t0)
        np.testing.assert_array_equal(res.left_take, olt)
        np.testing.assert_array_equal(res.right_take, ort)
        if opts is None:
            walls[skew] = best
        rows.append({
            "metric": f"fact x dim join ({skew}{'' if opts is None else ' partitioned x4'})",
            "fact_rows": fact_batch.num_rows,
            "dim_rows": dim_batch.num_rows,
            "matches": int(res.num_rows),
            "device_wall_s": round(best, 4),
            "host_wall_s": round(host_wall, 4),
            "device_rows_per_sec": round(fact_batch.num_rows / best, 1),
            "speedup_vs_host": round(host_wall / best, 2),
            "algorithm": res.stats["algorithm"],
            "engine": res.stats["engine"],
            "partitions": res.stats["partitions"],
            "skew_keys_split": res.stats["skew_keys"],
        })
    g = join_metrics()
    breakdown = {
        "metric": "join breakdown",
        **{
            k: g.counter(k).count
            for k in (
                "joins", "rows_probed", "rows_matched", "hash_joins",
                "sort_merge_joins", "code_domain_joins", "skew_keys",
                "skew_split_rows",
            )
        },
    }
    return rows, breakdown, walls


def run_headline(iters=2, n_fact=300_000, n_dim=30_000):
    """Scaled spot-check for bench.py: in-memory code-backed fact x dim
    (the shape the dict-domain reader delivers), device vs host loop,
    output asserted identical."""
    import paimon_tpu as pt
    from paimon_tpu.data.batch import Column, ColumnBatch
    from paimon_tpu.metrics import join_metrics, registry
    from paimon_tpu.ops.join import join_batches

    rng = np.random.default_rng(5)
    pool = np.array([f"C{i:06d}" for i in range(n_dim)], dtype=object)
    fact_codes = rng.integers(0, n_dim, n_fact).astype(np.uint32)
    dim_codes = np.arange(n_dim, dtype=np.uint32)
    fact = ColumnBatch(
        pt.RowType.of(("cust", pt.STRING(False)), ("amount", pt.DOUBLE())),
        {"cust": Column.from_codes(pool, fact_codes), "amount": Column(rng.random(n_fact))},
    )
    dim = ColumnBatch(
        pt.RowType.of(("cid", pt.STRING(False)), ("rate", pt.DOUBLE())),
        {"cid": Column.from_codes(pool, dim_codes), "rate": Column(rng.random(n_dim))},
    )
    registry.reset()
    cust = [pool[c] for c in fact_codes]
    olt, ort, host_wall = host_row_at_a_time(cust, pool.tolist())
    best = float("inf")
    for _ in range(max(iters, 1) + 1):
        t0 = time.perf_counter()
        res = join_batches(fact, dim, ["cust"], ["cid"], how="inner")
        best = min(best, time.perf_counter() - t0)
    np.testing.assert_array_equal(res.left_take, olt)
    np.testing.assert_array_equal(res.right_take, ort)
    g = join_metrics()
    assert g.counter("code_domain_joins").count > 0
    return [
        {
            "metric": f"device join vs host row-at-a-time ({n_fact // 1000}k x {n_dim // 1000}k, code-domain key)",
            "device_rows_per_sec": round(n_fact / best, 1),
            "host_rows_per_sec": round(n_fact / host_wall, 1),
            "speedup": round(host_wall / best, 2),
            "unit": "rows/s",
        },
        {
            "metric": "join breakdown",
            **{
                k: g.counter(k).count
                for k in (
                    "joins", "rows_probed", "rows_matched", "hash_joins",
                    "sort_merge_joins", "code_domain_joins", "skew_keys",
                )
            },
            "unit": "counters",
        },
    ]


def main():
    tmp = tempfile.mkdtemp(prefix="paimon_join_bench_")
    try:
        t0 = time.perf_counter()
        fact, dim = build_tables(tmp)
        print(f"# tables built in {time.perf_counter() - t0:.1f}s", file=sys.stderr)
        fact_batch, dim_batch = _read(fact), _read(dim)
        rows, breakdown, walls = run(fact_batch, dim_batch)
        uniform = next(r for r in rows if "uniform" in r["metric"])
        degradation = walls["hot50"] / walls["uniform"]
        summary = {
            "metric": "join headline",
            "speedup_vs_host_uniform": uniform["speedup_vs_host"],
            "skew_degradation_hot50_vs_uniform": round(degradation, 3),
            "targets": {"speedup_vs_host": ">= 5", "skew_degradation": "<= 2"},
        }
        for row in rows + [breakdown, summary]:
            print(json.dumps(row))
        os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
        with open(RESULTS, "w") as f:
            json.dump({"rows": rows, "breakdown": breakdown, "summary": summary}, f, indent=2)
        assert breakdown["code_domain_joins"] > 0, "code-domain join never fired"
        assert breakdown["skew_keys"] >= 1, "the partitioned pass never split the hot key"
        assert uniform["speedup_vs_host"] >= 5, uniform
        assert degradation <= 2.0, degradation
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    from paimon_tpu.utils import enable_compile_cache

    enable_compile_cache()
    main()
