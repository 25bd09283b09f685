#!/usr/bin/env python
"""Benchmark: production mega-soak — every plane of the stack on one table
set, one composed chaos store, one oracle, one verdict.

Runs the service.mega_soak supervisor (cluster coordinator + worker OS
processes on the mesh engine, the multi-tenant gateway front door, journaled
writer / getter / subscriber / distributed-SQL OS processes, snapshot-expiry
+ consumer-expiry + orphan-sweep churn) over the full scenario matrix in two
configurations:

  full        the whole DEFAULT_MATRIX (flagship cluster+branch/tag cell,
              dict-dynamic consumer-expiry cell, wide-pallas cell,
              native-legacy engine-contrast cell), >= 10 min total at the
              default chaos shaping (1 op in 200 faulting, latency on every
              read/write), scripted kill -9 deaths at every registered
              crash point plus seeded random SIGKILLs. The headline: kills
              survived across >= 3 process kinds and >= 4 distinct crash
              points with ONE consistent:true verdict — 0 lost/duplicated/
              mismatched rows, 0 untyped sheds, 0 pinned-read errors,
              post-sweep disk set == reachable closure, and every metric
              group (io/soak/get/sub/cluster/sql/gateway/compaction/dict/
              pallas) nonzero somewhere in the run.
  seed        the contrast run WITHOUT the resilience stack (fs.retry.
              max-attempts=1, commit.max-retries=0) on one cell at a hotter
              fault rate: the same chaos store now surfaces raw IO faults
              to every plane and the verdict goes inconsistent — recorded
              in the results JSON so the delta is auditable.

Prints one JSON line per configuration and writes
benchmarks/results/mega_soak_bench.json.

    python benchmarks/mega_soak_bench.py [--duration 150] [--seed 0]
"""

import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

KEEP = [
    "wall_s",
    "consistent",
    "kills_total",
    "kills_by_kind",
    "kills_by_point",
    "process_kinds_killed",
    "crash_points_fired",
    "metric_groups",
    "procs_spawned",
    "procs_killed",
    "procs_respawned",
    "child_errors",
    "snapshot_expiries",
    "faults_injected",
]

CELL_KEEP = [
    "cell",
    "consistent",
    "accepted_commits",
    "final_rows",
    "total_record_count",
    "record_count_matches",
    "lost_rows",
    "duplicated_rows",
    "wrong_values",
    "gw_sheds_untyped",
    "pinned_read_errors",
    "getter_read_errors",
    "sql_client_errors",
    "sub_mismatches",
    "leaked_file_count",
]


def run_full(duration_per_cell: float, seed: int, workers: int) -> dict:
    from paimon_tpu.service.mega_soak import MegaConfig, run_mega_soak

    cfg = MegaConfig(duration_s=duration_per_cell, cluster_workers=workers, seed=seed)
    tmp = tempfile.mkdtemp(prefix="paimon_mega_bench_full_")
    try:
        report = run_mega_soak(tmp, cfg)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    row = {
        "metric": "production mega-soak (cluster + gateway + subscribers + SQL + churn, one chaos store)",
        "mode": "full (journal recovery + fs.retry + typed sheds + orphan sweeps)",
        **{k: report.get(k) for k in KEEP},
        "cells": [{k: c.get(k) for k in CELL_KEEP} for c in report["cells"]],
    }
    # the acceptance gate (ISSUE 18): >= 10 kills over >= 3 process kinds
    # and >= 4 distinct crash points, one clean verdict, every metric
    # group ticking somewhere in the matrix
    assert report["consistent"], report
    assert report["kills_total"] >= 10, report
    assert len(report["process_kinds_killed"]) >= 3, report
    assert len(report["crash_points_fired"]) >= 4, report
    for cell in report["cells"]:
        assert cell["lost_rows"] == 0 and cell["duplicated_rows"] == 0, cell
        assert cell["wrong_values"] == 0, cell
        assert cell["gw_sheds_untyped"] == 0, cell
        assert cell["pinned_read_errors"] == 0, cell
        assert cell["leaked_file_count"] == 0, cell
    dead = [g for g, n in report["metric_groups"].items() if n == 0]
    assert not dead, f"metric groups never ticked: {dead}"
    return row


def run_seed(duration: float, seed: int) -> dict:
    from paimon_tpu.service.mega_soak import DEFAULT_MATRIX, MegaConfig, run_mega_soak

    # one non-cluster cell, retries off, hotter faults: the point is the
    # contrast, not ten minutes of a known-broken configuration
    cell = tuple(s for s in DEFAULT_MATRIX if s.name == "dict-dynamic")
    cfg = MegaConfig(
        duration_s=duration,
        seed=seed,
        scenarios=cell,
        chaos_possibility=80,
        table_options={"fs.retry.max-attempts": "1", "commit.max-retries": "0"},
    )
    tmp = tempfile.mkdtemp(prefix="paimon_mega_bench_seed_")
    try:
        report = run_mega_soak(tmp, cfg)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    row = {
        "metric": "production mega-soak (single cell, same chaos store and kill schedule)",
        "mode": "seed (fs.retry.max-attempts=1, commit.max-retries=0)",
        **{k: report.get(k) for k in KEEP},
        "cells": [{k: c.get(k) for k in CELL_KEEP} for c in report["cells"]],
    }
    # the contrast gate: without retries the same chaos store demonstrably
    # breaks SOMETHING the full stack keeps clean — an untyped escape, a
    # failed plane, or a dirty verdict
    c = report["cells"][0]
    degraded = (
        not report["consistent"]
        or (c.get("gw_sheds_untyped") or 0) > 0
        or (c.get("pinned_read_errors") or 0) > 0
        or (c.get("getter_read_errors") or 0) > 0
        or (report.get("child_errors") or 0) > 0
    )
    assert degraded, report
    return row


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")  # host-side soak: never grab the chip
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--duration", type=float, default=150.0, help="seconds per matrix cell (4 cells)"
    )
    ap.add_argument("--seed-duration", type=float, default=30.0, help="contrast run length")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-seed", action="store_true", help="skip the contrast row")
    args = ap.parse_args()
    rows = [run_full(args.duration, args.seed, args.workers)]
    print(json.dumps(rows[0]))
    if not args.no_seed:
        rows.append(run_seed(args.seed_duration, args.seed))
        print(json.dumps(rows[1]))
    out = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "results", "mega_soak_bench.json"
    )
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=2)


if __name__ == "__main__":
    from paimon_tpu.utils import enable_compile_cache

    enable_compile_cache()
    main()
