#!/usr/bin/env python
"""Benchmark: merge-on-read throughput (BASELINE.json config #1).

Mirrors the reference micro-benchmark (paimon-micro-benchmarks
TableReadBenchmark: 1M-row primary-key table, single bucket, full scan
through the Table API — write, then scan -> plan -> merge-read). The table is
written as 4 overlapping sorted runs (write-only mode, no compaction), so the
read path genuinely k-way-merges 1M keyed rows: columnar decode -> key-lane
encode -> device sort+segment kernel -> gather.

Baseline denominator: Parquet full scan 975.4 Krows/s on Apple M1 Pro JDK8
(reference TableReadBenchmark.java:62-68; see /root/repo/BASELINE.md).

Prints ONE json line: {"metric", "value", "unit", "vs_baseline"}.
"""

import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from paimon_tpu.utils import enable_compile_cache, require_device

enable_compile_cache()

# a measurement names the device it ran on, and fails where there is no TPU
# (JAX_PLATFORMS=cpu measures the CPU on purpose; the rows then say cpu)
_PLATFORM, _DEVICE_KIND, _DEVICE_COUNT = require_device()

BASELINE_ROWS_PER_SEC = 975_400.0
N_ROWS = 1_000_000
N_RUNS = 4


def build_table(path: str):
    import paimon_tpu as pt
    from paimon_tpu.catalog import FileSystemCatalog

    cat = FileSystemCatalog(path, commit_user="bench")
    schema = pt.RowType.of(
        ("id", pt.BIGINT(False)),
        ("c1", pt.BIGINT()),
        ("c2", pt.BIGINT()),
        ("c3", pt.BIGINT()),
        ("d1", pt.DOUBLE()),
        ("d2", pt.DOUBLE()),
        ("s1", pt.STRING()),
        ("s2", pt.STRING()),
    )
    table = cat.create_table(
        "bench.t",
        schema,
        primary_keys=["id"],
        options={"bucket": "1", "file.format": "parquet", "write-only": "true"},
    )
    rng = np.random.default_rng(7)
    ids = rng.permutation(N_ROWS).astype(np.int64)
    per = N_ROWS // N_RUNS
    for r in range(N_RUNS):
        chunk = np.sort(ids[r * per : (r + 1) * per])
        n = len(chunk)
        wb = table.new_batch_write_builder()
        w = wb.new_write()
        w.write(
            {
                "id": chunk,
                "c1": chunk * 3,
                "c2": chunk % 97,
                "c3": chunk // 7,
                "d1": chunk.astype(np.float64) * 0.5,
                "d2": chunk.astype(np.float64) + 0.25,
                "s1": np.array([f"val-{int(x) % 1000:04d}" for x in chunk], dtype=object),
                "s2": np.array([f"tag-{int(x) % 10}" for x in chunk], dtype=object),
            }
        )
        wb.new_commit().commit(w.prepare_commit())
    return table


def bench_read(table) -> float:
    rb = table.new_read_builder()
    best = float("inf")
    # first iteration warms jit caches
    for it in range(7):
        t0 = time.perf_counter()
        splits = rb.new_scan().plan()
        out = rb.new_read().read_all(splits)
        dt = time.perf_counter() - t0
        assert out.num_rows == N_ROWS, out.num_rows
        if it > 0:
            best = min(best, dt)
    return N_ROWS / best


def bench_decode(table) -> dict:
    """One native-decoder pass over the standard merge-read table: the
    per-stage decode breakdown (pages decoded/skipped, bytes expanded, wall
    millis) from the decode{} metric group (benchmarks/decode_bench.py is
    the dedicated per-encoding comparison)."""
    from paimon_tpu.metrics import decode_metrics

    native = table.copy(
        {"format.parquet.decoder": "native", "cache.data-file.max-memory-size": "0 b"}
    )
    rb = native.new_read_builder()
    g = decode_metrics()
    c0 = {k: g.counter(k).count for k in ("pages_decoded", "pages_skipped", "bytes_expanded", "files_fallback")}
    t0 = time.perf_counter()
    out = rb.new_read().read_all(rb.new_scan().plan())
    dt = time.perf_counter() - t0
    assert out.num_rows == N_ROWS, out.num_rows
    return {
        "metric": "native decode breakdown (full scan)",
        "pages_decoded": g.counter("pages_decoded").count - c0["pages_decoded"],
        "pages_skipped": g.counter("pages_skipped").count - c0["pages_skipped"],
        "bytes_expanded": g.counter("bytes_expanded").count - c0["bytes_expanded"],
        "files_fallback": g.counter("files_fallback").count - c0["files_fallback"],
        "wall_ms": round(dt * 1000, 1),
        "unit": "counters",
    }


def bench_scan_cache(table) -> float:
    """Cold-vs-warm repeated scan (plan + read_all) through the byte-budget
    caches (benchmarks/scan_cache.py is the dedicated micro-benchmark; this
    line tracks the same effect on the standard merge-read table)."""
    from paimon_tpu.utils import cache as cache_mod

    cached = table.copy(
        {"cache.manifest.max-memory-size": "256 mb", "cache.data-file.max-memory-size": "1 gb"}
    )
    rb = cached.new_read_builder()

    def once() -> float:
        t0 = time.perf_counter()
        out = rb.new_read().read_all(rb.new_scan().plan())
        assert out.num_rows == N_ROWS, out.num_rows
        return time.perf_counter() - t0

    cache_mod.clear_all()
    cold = once()
    once()  # populate + warm
    warm = min(once() for _ in range(3))
    return cold / warm if warm > 0 else float("inf")


def bench_pipeline() -> list:
    """Pipelined split scheduler spot-check (benchmarks/pipeline_bench.py is
    the dedicated benchmark): 8-bucket cold scan, pipelined vs
    scan.prefetch-splits=0, on local fs (no-regression guard) and behind a
    simulated object-store read RTT (the latency the pipeline exists to
    hide). Each row asserts bit-identical output and the bounded queue-depth
    high-water."""
    import importlib.util

    p = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks", "pipeline_bench.py")
    spec = importlib.util.spec_from_file_location("_pipeline_bench", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # the dedicated bench's representative size: below ~2 MB/scan the fixed
    # thread-spawn cost dominates on a single-core host and the row would
    # measure overhead, not overlap
    return mod.run(iters=2)


def bench_encode() -> list:
    """Write-path headline (benchmarks/encode_bench.py is the dedicated
    benchmark): ingest throughput for a 1M-row PK write+flush, arrow vs
    native encoder, plus the native encode counter breakdown — the write
    mirror of the decode rows. The guard inside run_headline asserts
    pyarrow reads every natively-written file bit-identically."""
    import importlib.util

    p = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks", "encode_bench.py")
    spec = importlib.util.spec_from_file_location("_encode_bench", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.run_headline(iters=2)


def bench_lanes(table) -> list:
    """Key-lane compression breakdown (benchmarks/lanes_bench.py is the
    dedicated 3-schema x 3-workload sweep): the standard merge-read table
    read twice through table.copy — merge.lane-compression off vs on (same
    files, same cache state) — plus the planner counter deltas from the
    lanes{} metric group. Outputs are asserted identical row-for-row."""
    from paimon_tpu.metrics import lanes_metrics

    g = lanes_metrics()

    def counters():
        return {k: g.counter(k).count for k in ("plans", "lanes_in", "lanes_out", "ovc_merges", "bytes_saved")}

    results = {}
    deltas = None
    for comp in (False, True):
        t = table.copy({"merge.lane-compression": "true" if comp else "false"})
        rb = t.new_read_builder()
        best = float("inf")
        c0 = counters()
        out = None
        for it in range(4):
            t0 = time.perf_counter()
            out = rb.new_read().read_all(rb.new_scan().plan())
            dt = time.perf_counter() - t0
            assert out.num_rows == N_ROWS, out.num_rows
            if it > 0:
                best = min(best, dt)
        if comp:
            c1 = counters()
            deltas = {k: c1[k] - c0[k] for k in c0}
        results[comp] = (N_ROWS / best, out)
    assert results[True][1].to_pylist() == results[False][1].to_pylist()
    on, off = results[True][0], results[False][0]
    plans = max(deltas["plans"], 1)
    return [
        {
            "metric": "merge-read compressed vs uncompressed key lanes (same table)",
            "rows_per_sec_uncompressed": round(off, 1),
            "rows_per_sec_compressed": round(on, 1),
            "speedup": round(on / off, 3),
            "unit": "rows/s",
        },
        {
            "metric": "key-lane compression breakdown",
            "plans": deltas["plans"],
            "lanes_in_per_plan": round(deltas["lanes_in"] / plans, 2),
            "lanes_out_per_plan": round(deltas["lanes_out"] / plans, 2),
            "ovc_merges": deltas["ovc_merges"],
            "bytes_saved": deltas["bytes_saved"],
            "unit": "counters",
        },
    ]


def bench_dicts(table) -> list:
    """Compressed-domain merge spot-check (benchmarks/dict_domain_bench.py
    is the dedicated 3-schema x 3-workload sweep with the >=2x compaction
    headline): the standard merge-read table read through table.copy with
    merge.dict-domain off vs on — same files, same cache state — plus the
    dict{} counter breakdown. Outputs are asserted identical row-for-row."""
    from paimon_tpu.metrics import dict_metrics

    g = dict_metrics()

    def counters():
        return {
            k: g.counter(k).count
            for k in ("pools_unified", "codes_remapped", "rows_code_domain", "fallback_expanded")
        }

    results = {}
    deltas = None
    for dd in (False, True):
        t = table.copy(
            {
                "merge.dict-domain": "true" if dd else "false",
                "format.parquet.decoder": "native",
                "format.parquet.encoder": "native",
                "cache.data-file.max-memory-size": "0 b",
            }
        )
        rb = t.new_read_builder()
        best = float("inf")
        c0 = counters()
        out = None
        for it in range(4):
            t0 = time.perf_counter()
            out = rb.new_read().read_all(rb.new_scan().plan())
            out.to_arrow()  # delivery included: the code domain hands arrow dictionaries
            dt = time.perf_counter() - t0
            assert out.num_rows == N_ROWS, out.num_rows
            if it > 0:
                best = min(best, dt)
        if dd:
            deltas = {k: v - c0[k] for k, v in counters().items()}
        results[dd] = (N_ROWS / best, out)
    assert results[True][1].to_pylist() == results[False][1].to_pylist()
    on, off = results[True][0], results[False][0]
    return [
        {
            "metric": "merge-read dict-domain on vs off (same table, native decode)",
            "rows_per_sec_expanded": round(off, 1),
            "rows_per_sec_code_domain": round(on, 1),
            "speedup": round(on / off, 3),
            "unit": "rows/s",
        },
        {
            "metric": "compressed-domain merge breakdown",
            "pools_unified": deltas["pools_unified"],
            "codes_remapped": deltas["codes_remapped"],
            "rows_code_domain": deltas["rows_code_domain"],
            "fallback_expanded": deltas["fallback_expanded"],
            "unify_ms_mean": round(dict_metrics().histogram("unify_ms").mean, 3),
            "unit": "counters",
        },
    ]


def bench_pallas(table) -> list:
    """Pallas sort-engine spot-check: the standard merge-read table read
    through table.copy with sort-engine pallas (lax.sort + the pallas
    boundary-sweep kernel; interpreted on the CPU, Mosaic-compiled on a
    TPU) vs xla-segmented. Outputs asserted identical row-for-row, plus the
    pallas{} counter breakdown."""
    from paimon_tpu.metrics import pallas_metrics

    g = pallas_metrics()

    def counters():
        return {k: g.counter(k).count for k in ("kernels_launched", "tiles")}

    results = {}
    deltas = None
    for engine in ("xla-segmented", "pallas"):
        t = table.copy({"sort-engine": engine})
        rb = t.new_read_builder()
        best = float("inf")
        c0 = counters()
        out = None
        for it in range(3):
            t0 = time.perf_counter()
            out = rb.new_read().read_all(rb.new_scan().plan())
            dt = time.perf_counter() - t0
            assert out.num_rows == N_ROWS, out.num_rows
            if it > 0:
                best = min(best, dt)
        if engine == "pallas":
            deltas = {k: v - c0[k] for k, v in counters().items()}
        results[engine] = (N_ROWS / best, out)
    assert results["pallas"][1].to_pylist() == results["xla-segmented"][1].to_pylist()
    pal, xla = results["pallas"][0], results["xla-segmented"][0]
    return [
        {
            "metric": "merge-read sort-engine pallas vs xla-segmented (same table)",
            "rows_per_sec_xla_segmented": round(xla, 1),
            "rows_per_sec_pallas": round(pal, 1),
            "speedup": round(pal / xla, 3),
            "identical_output": True,
            "unit": "rows/s",
        },
        {
            "metric": "pallas kernel breakdown",
            "kernels_launched": deltas["kernels_launched"],
            "tiles": deltas["tiles"],
            "unit": "counters",
        },
    ]


def bench_join() -> list:
    """Device-join spot-check (benchmarks/join_bench.py is the dedicated
    1M x 100k fact x dimension sweep with the >=5x headline and the skew
    degradation bound): a scaled code-domain-key join, device kernel vs the
    host row-at-a-time dict loop, output asserted identical, plus the
    join{} counter breakdown (code_domain_joins must be > 0)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "join_bench", os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks", "join_bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.run_headline(iters=2)


def bench_point_get() -> list:
    """Batched point-get spot-check (benchmarks/point_get_bench.py is the
    dedicated benchmark with the 30 s mixed soak row): 10k-key get_batch vs
    the scalar lookup() loop on a 1M-row PK table (every pass asserting
    identical results), the bloom key-index pruning contrast on a sparse
    absent-key set, and the get{} counter breakdown."""
    import importlib.util

    p = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks", "point_get_bench.py")
    spec = importlib.util.spec_from_file_location("_point_get_bench", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.run_headline(iters=2)


def bench_subscribe() -> list:
    """CDC subscription fan-out spot-check (benchmarks/subscribe_bench.py is
    the dedicated 1/8/32/128-subscriber sweep): 32 subscribers on one
    decode-once hub vs 32 independent StreamTableScan loops (shared decode
    cache off — the N-separate-processes model), every subscriber asserting
    it received every snapshot, plus the decode{pages_decoded} flatness
    counters and per-subscriber p99 delivery lag."""
    import importlib.util

    p = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks", "subscribe_bench.py")
    spec = importlib.util.spec_from_file_location("_subscribe_bench", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.run_headline(iters=1)


def bench_adaptive() -> dict:
    """Adaptive-vs-inline compaction spot-check (benchmarks/
    adaptive_compact_bench.py is the dedicated 60 s skewed soak with the
    >=1.2x headline): a short two-mode run — inline compaction in the
    writers vs the LUDA-style background scheduler with debt admission —
    reporting sustained ingest, the read-amp bound, and the zero-lost/dup
    invariants."""
    import importlib.util

    p = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks", "adaptive_compact_bench.py"
    )
    spec = importlib.util.spec_from_file_location("_adaptive_bench", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    inline = mod.run_mode("inline", duration=8.0, seed=0)
    adaptive = mod.run_mode("adaptive", duration=8.0, seed=0)
    clean = all(
        r["lost_rows"] == 0 and r["duplicated_rows"] == 0 and r["wrong_values"] == 0
        for r in (inline, adaptive)
    )
    return {
        "metric": "adaptive vs inline compaction (8 s skewed soak spot-check)",
        "rows_per_sec_inline": inline["rows_per_sec"],
        "rows_per_sec_adaptive": adaptive["rows_per_sec"],
        "speedup": round(adaptive["rows_per_sec"] / max(inline["rows_per_sec"], 1e-9), 3),
        "read_amp_p99_inline": inline["read_amp_p99"],
        "read_amp_p99_adaptive": adaptive["read_amp_p99"],
        "read_amp_ceiling": adaptive.get("read_amp_ceiling"),
        "adaptive_runs": adaptive.get("adaptive_runs"),
        "zero_lost_dup": clean,
        "unit": "counters",
    }


def bench_mesh() -> list:
    """Mesh-sharded execution headline (benchmarks/multichip_bench.py is the
    dedicated 1/2/4/8-device sweep): 8-bucket merge-read behind simulated
    store RTT at 8 simulated devices vs 1, each device count in its own
    subprocess with a forced host device count — every pass asserts the mesh
    output bit-identical to the single-device engine before timing counts —
    plus the mesh{} counter breakdown. Subprocess children pin
    JAX_PLATFORMS=cpu (a chip belongs to one process), so this row says cpu
    whatever the parent runs on."""
    import importlib.util

    p = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks", "multichip_bench.py")
    spec = importlib.util.spec_from_file_location("_multichip_bench", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.run_headline(iters=2)


def bench_sql_cluster() -> list:
    """Distributed SQL spot-check (benchmarks/sql_cluster_bench.py is the
    dedicated 1/2/4-worker sweep with the >=3x headline): scatter-gather
    aggregate queries against serve-mode worker OS processes behind a
    latency-shaped store, every timed pass asserting the distributed result
    bit-identical to the single-process evaluator and that partial
    aggregates really reduced on workers (sql{rows_reduced_device})."""
    import importlib.util

    p = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks", "sql_cluster_bench.py")
    spec = importlib.util.spec_from_file_location("_sql_cluster_bench", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.run_headline(iters=2)


def bench_sql_local_groupby() -> list:
    """Single-process high-cardinality GROUP BY no-regression guard
    (benchmarks/sql_shuffle_bench.py is the dedicated 4-worker shuffle rig
    with the >=2x coordinator-combine-stage headline): times the LOCAL
    segment-reduce path at >=100k distinct groups — the pure path the
    shuffle plane must not disturb — asserted within ~1.1x the measured
    baseline."""
    import importlib.util

    p = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks", "sql_shuffle_bench.py")
    spec = importlib.util.spec_from_file_location("_sql_shuffle_bench", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.run_local_headline(iters=2)


def bench_scan_plan() -> list:
    """Scan-planning scale spot-check (benchmarks/scan_plan_bench.py is the
    dedicated rig): plan latency over a 10k-entry live manifest set built
    through the real commit path, full and partition-pruned, against a
    stated metadata-only budget."""
    import importlib.util

    p = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks", "scan_plan_bench.py")
    spec = importlib.util.spec_from_file_location("_scan_plan_bench", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.run_headline(iters=2)


def bench_gateway() -> list:
    """Gateway hedged-read spot-check (benchmarks/gateway_bench.py is the
    dedicated rig): one latency-shamed worker in a 2-worker cluster, the
    same probe sequence through an unhedged and a hedged Gateway, results
    asserted bit-identical to the formula oracle and the hedge budget
    (gateway.hedge.max-fraction) asserted respected."""
    import importlib.util

    p = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks", "gateway_bench.py")
    spec = importlib.util.spec_from_file_location("_gateway_bench", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.run_headline(iters=2)


def bench_elastic() -> list:
    """Elastic-cluster spot-check (benchmarks/elastic_bench.py is the
    dedicated rig): a live 8->16 bucket rescale under continuous ingest
    (zero lost/dup rows, serving p99 <= 2x steady-state), a 2->4 worker
    scale-out through the join-steal handoff, and hot-bucket replicated
    serving asserted >= 2x single-owner throughput with every pass
    bit-identical to the primary and the oracle."""
    import importlib.util

    p = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks", "elastic_bench.py")
    spec = importlib.util.spec_from_file_location("_elastic_bench", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.run_headline(iters=1)


def bench_resilience() -> dict:
    """Commit resilience spot-check (benchmarks/resilience_bench.py is the
    dedicated rate-sweep): 25 small commits at a 5% injected transient-fault
    rate through the retry stack. failed_commits must stay 0; the retry/
    giveup counters make resilience regressions visible in BENCH_* exactly
    like perf regressions."""
    import importlib.util

    p = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks", "resilience_bench.py")
    spec = importlib.util.spec_from_file_location("_resilience_bench", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    row = mod.run_config(0.05, 20, True)
    return {
        "metric": "commit resilience (5% injected transient faults)",
        "commits": row["commits"],
        "failed_commits": row["failed_commits"],
        "io_retries": row["io_retries"],
        "io_giveups": row["io_giveups"],
        "commits_per_sec": row["commits_per_sec"],
        "unit": "counters",
    }


def bench_soak() -> dict:
    """Traffic-soak spot-check (benchmarks/soak_bench.py is the dedicated
    >=60 s run): a short multi-writer/multi-reader soak at 5% injected
    faults with admission control on. consistent must stay true and
    failed/lost/leaked must stay 0 — the composed-system invariants live in
    BENCH_* next to the perf rows."""
    import importlib.util

    p = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks", "soak_bench.py")
    spec = importlib.util.spec_from_file_location("_soak_bench", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    row = mod.run_mode("full", duration=8.0, possibility=20, seed=0)
    return {
        "metric": "traffic soak spot-check (8 s, 3 writers / 2 readers, 5% faults)",
        "consistent": row["consistent"],
        "commits_ok": row["commits_ok"],
        "failed_commits": row["commits_failed"],
        "commits_per_sec": row["commits_per_sec"],
        "read_p99_ms": row["read_p99_ms"],
        "writes_throttled": row["writes_throttled"],
        "lost_rows": row["lost_rows"],
        "leaked_files": row["leaked_file_count"],
        "unit": "counters",
    }


def bench_mega() -> dict:
    """Mega-soak spot-check (benchmarks/mega_soak_bench.py is the dedicated
    full-matrix >=10 min run): one scenario cell — dynamic buckets, every
    plane live (gateway writers, getters, subscribers, SQL, churn) — on the
    composed chaos store with the scripted kill schedule armed. The one
    verdict must stay consistent:true with 0 untyped sheds."""
    from paimon_tpu.service.mega_soak import DEFAULT_MATRIX, MegaConfig, run_mega_soak

    cell = tuple(s for s in DEFAULT_MATRIX if s.name == "dict-dynamic")
    # expiry knobs scaled to the short cell: the decoy-consumer check needs
    # consumer_expire_ms + an expiry pass to fit inside the duration
    cfg = MegaConfig(
        duration_s=12.0,
        seed=0,
        scenarios=cell,
        kill_period_s=6.0,
        expire_period_s=3.0,
        consumer_expire_ms=4_000,
    )
    tmp = tempfile.mkdtemp(prefix="paimon_tpu_bench_mega_")
    try:
        report = run_mega_soak(tmp, cfg)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    c = report["cells"][0]
    return {
        "metric": "mega-soak spot-check (12 s, dict-dynamic cell, chaos store + kill schedule)",
        "consistent": report["consistent"],
        "kills": report["kills_total"],
        "accepted_commits": c.get("accepted_commits"),
        "final_rows": c.get("final_rows"),
        "lost_rows": c.get("lost_rows"),
        "duplicated_rows": c.get("duplicated_rows"),
        "gw_sheds_untyped": c.get("gw_sheds_untyped"),
        "leaked_files": c.get("leaked_file_count"),
        "unit": "counters",
    }


def main():
    tmp = tempfile.mkdtemp(prefix="paimon_tpu_bench_")
    try:
        table = build_table(tmp)
        rows_per_sec = bench_read(table)
        scan_cache_speedup = bench_scan_cache(table)
        decode_row = bench_decode(table)
        lanes_rows = bench_lanes(table)
        dict_rows = bench_dicts(table)
        join_rows = bench_join()
        point_get_rows = bench_point_get()
        subscribe_rows = bench_subscribe()
        pallas_rows = bench_pallas(table)
        adaptive_row = bench_adaptive()
        pipeline_rows = bench_pipeline()
        encode_rows = bench_encode()
        mesh_rows = bench_mesh()
        sql_cluster_rows = bench_sql_cluster()
        sql_local_groupby_rows = bench_sql_local_groupby()
        scan_plan_rows = bench_scan_plan()
        gateway_rows = bench_gateway()
        elastic_rows = bench_elastic()
        resilience_row = bench_resilience()
        soak_row = bench_soak()
        mega_row = bench_mega()
        device = {"platform": _PLATFORM, "device_kind": _DEVICE_KIND, "devices": _DEVICE_COUNT}
        # rows measured in CPU-pinned child processes (cluster workers, soak
        # and mesh-sweep children: a chip belongs to one process) say cpu,
        # whatever this parent runs on
        child_cpu = {"platform": "cpu", "device_kind": "cpu (child processes)"}
        in_process = [
            {
                "metric": "merge-read throughput (1M-row PK table, 4 sorted runs, parquet, 1 bucket)",
                "value": round(rows_per_sec, 1),
                "unit": "rows/s",
                "vs_baseline": round(rows_per_sec / BASELINE_ROWS_PER_SEC, 3),
            },
            {
                "metric": "repeated-scan speedup (warm cache)",
                "value": round(scan_cache_speedup, 2),
                "unit": "x",
            },
            decode_row,
            *lanes_rows,
            *dict_rows,
            *join_rows,
            *point_get_rows,
            *subscribe_rows,
            *pallas_rows,
            adaptive_row,
            *pipeline_rows,
            *encode_rows,
            *sql_local_groupby_rows,  # in-process only: no cluster is started
            *scan_plan_rows,
            resilience_row,
        ]
        in_children = [
            *mesh_rows, *sql_cluster_rows, *gateway_rows, *elastic_rows, soak_row, mega_row,
        ]
        for r in in_process:
            print(json.dumps(dict(r, **device)))
        for r in in_children:
            print(json.dumps(dict(r, **child_cpu)))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
