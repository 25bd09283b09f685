#!/usr/bin/env python
"""Benchmark: scan-planning latency at high manifest scale (ISSUE 20
satellite; round-5 verdict Missing #6).

Builds a partitioned append-only table through the REAL commit path until
the live manifest set holds >= FILES data-file entries (default 10k:
COMMITS commits x PARTS partitions, one file each), then times
`new_read_builder().new_scan().plan()`:

  * full      — plan every entry (the coordinator's cost to open a scan
                over the whole table; this is what cluster_query pays
                before any fragment is dispatched)
  * pruned    — plan under a single-partition predicate (manifest entry
                stats must prune ~all files; measures the skipping path,
                not just the happy case). NOTE: pruning costs MORE than
                the unfiltered plan today — the partition predicate is
                evaluated per manifest entry on the host — so both rows
                gate against the same absolute budget, and the ratio is
                recorded for the day entry-level pruning is vectorized.

Both are best-of ITERS wall seconds against a stated budget. Planning is
pure metadata work — no data file is opened — so the budget holds on a
1-core CI container. Results land in benchmarks/results/scan_plan_bench.json.
"""

import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

PARTS = int(os.environ.get("PAIMON_TPU_SCANPLAN_PARTS", "500"))
COMMITS = int(os.environ.get("PAIMON_TPU_SCANPLAN_COMMITS", "20"))
FILES = PARTS * COMMITS
ITERS = int(os.environ.get("PAIMON_TPU_SCANPLAN_ITERS", "3"))
# metadata-only work: generous for a 1-core CI box, tight enough to catch
# an accidental O(files^2) or per-entry IO regression
PLAN_BUDGET_S = float(os.environ.get("PAIMON_TPU_SCANPLAN_BUDGET_S", "5.0"))
RESULTS = os.path.join(HERE, "results", "scan_plan_bench.json")


def _build(base: str):
    from paimon_tpu.catalog import FileSystemCatalog
    from paimon_tpu.types import BIGINT, DOUBLE, RowType

    cat = FileSystemCatalog(os.path.join(base, "wh"), commit_user="bench")
    t = cat.create_table(
        "db.plan",
        RowType.of(("p", BIGINT(False)), ("id", BIGINT()), ("v", DOUBLE())),
        partition_keys=("p",),
        options={"bucket": "1", "write-only": "true"},
    )
    ps = list(range(PARTS))
    for c in range(COMMITS):
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        w.write({"p": ps, "id": [c * PARTS + p for p in ps], "v": [float(c)] * PARTS})
        wb.new_commit().commit(w.prepare_commit())
    return t


def _best(fn) -> float:
    best = float("inf")
    for _ in range(ITERS):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run(iters: int = ITERS) -> dict:
    global ITERS
    ITERS = iters
    from paimon_tpu.data.predicate import equal

    base = tempfile.mkdtemp(prefix="paimon_scanplan_bench_")
    try:
        t0 = time.perf_counter()
        t = _build(base)
        build_s = time.perf_counter() - t0

        rb = t.new_read_builder()
        splits = rb.new_scan().plan()
        files = sum(len(s.files) for s in splits)
        assert files == FILES, f"expected {FILES} live files, planned {files}"
        full_s = _best(lambda: rb.new_scan().plan())

        rbp = t.new_read_builder().with_filter(equal("p", 7))
        pruned = rbp.new_scan().plan()
        pruned_files = sum(len(s.files) for s in pruned)
        assert pruned_files == COMMITS, (
            f"partition pruning kept {pruned_files} files, expected {COMMITS}"
        )
        pruned_s = _best(lambda: rbp.new_scan().plan())
    finally:
        shutil.rmtree(base, ignore_errors=True)

    row = {
        "metric": f"scan planning, {FILES} manifest entries ({COMMITS} commits x {PARTS} partitions)",
        "unit": "s/plan",
        "manifest_entries": FILES,
        "commits": COMMITS,
        "build_s": round(build_s, 2),
        "plan_full_s": round(full_s, 3),
        "plan_pruned_s": round(pruned_s, 3),
        "plan_budget_s": PLAN_BUDGET_S,
        "pruned_files": pruned_files,
        "pruned_over_full": round(pruned_s / full_s, 1) if full_s else None,
    }
    return {"row": row}


def run_headline(iters: int = 2) -> list:
    """bench.py hook: reduced iterations; gates live in main() only."""
    return [run(iters=iters)["row"]]


def main() -> None:
    res = run()
    row = res["row"]
    print(json.dumps(row))
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    with open(RESULTS, "w") as f:
        json.dump(res, f, indent=1)
    assert row["plan_full_s"] <= PLAN_BUDGET_S, (
        f"full scan plan over {row['manifest_entries']} manifest entries took "
        f"{row['plan_full_s']}s > {PLAN_BUDGET_S}s budget"
    )
    assert row["plan_pruned_s"] <= PLAN_BUDGET_S, (
        f"partition-pruned plan over {row['manifest_entries']} manifest entries "
        f"took {row['plan_pruned_s']}s > {PLAN_BUDGET_S}s budget"
    )


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from paimon_tpu.utils import enable_compile_cache

    enable_compile_cache()
    main()
