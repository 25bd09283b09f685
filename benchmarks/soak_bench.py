#!/usr/bin/env python
"""Benchmark: production traffic soak — sustained concurrent commits and
snapshot-consistent reads under injected faults.

Runs the service.soak harness (N committer threads on shared buckets, M
verified readers, a dedicated full-compactor and a snapshot expirer, one
shared WriteBufferController) in two configurations:

  full        >= 60 s at a 5% injected transient-fault rate with admission
              control + the full resilience stack. The headline: sustained
              commits/s and p99 read latency with 0 failed commits, 0 lost
              or duplicated rows (oracle-log verified), and a post-soak
              orphan sweep leaving the on-disk file set exactly equal to
              the reachable closure (0 leaked files).
  seed        the contrast run WITHOUT backpressure and without IO/CAS
              retries (fs.retry.max-attempts=1, commit.max-retries=0): at
              the same fault rate commits abort, reads error, and aborted
              rounds strew orphans — recorded in the results JSON so the
              delta is auditable.

and (unless --no-process) the PROCESS-GRAIN crash soak (service.proc_soak):

  proc-full   >= 60 s with 2 writer + 1 reader OS processes sharing only
              the warehouse filesystem, scripted kill -9 deaths at every
              commit/flush crash point plus seeded random SIGKILLs, respawn
              with journal recovery and periodic orphan sweeps. Headline:
              accepted commits/s and kills survived with 0 lost/duplicated
              rows (journal-oracle fold == final scan), 0 read errors, and
              0 leaked files after the final sweep.
  proc-seed   the contrast WITHOUT CAS retries, recovery probes, or orphan
              sweeps: the same kill schedule loses commits outright
              (rounds_failed), strands landed-but-unaccounted commits
              (rounds_ack_lost with zero crash_recoveries), and leaks the
              kills' torn files (leaked_file_count > 0).

Prints one JSON line per configuration and writes
benchmarks/results/soak_bench.json.

    python benchmarks/soak_bench.py [--duration 60] [--fault-possibility 20]
"""

import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_mode(mode: str, duration: float, possibility: int, seed: int) -> dict:
    from paimon_tpu.service.soak import SoakConfig, run_soak

    full = mode == "full"
    cfg = SoakConfig(
        duration_s=duration,
        writers=3,
        readers=2,
        fault_possibility=possibility,
        seed=seed,
        backpressure=full,
        resilient=full,
    )
    tmp = tempfile.mkdtemp(prefix=f"paimon_soak_bench_{mode}_")
    try:
        report = run_soak(tmp, cfg, domain=f"soakbench_{mode}_{seed}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    keep = [
        "wall_s",
        "consistent",
        "commits_ok",
        "commits_failed",
        "commits_conflict_survived",
        "commits_conflict_aborted",
        "commit_cas_retries",
        "commit_buckets_replanned",
        "accepted_commits",
        "accepted_rows",
        "commits_per_sec",
        "reads_ok",
        "read_errors",
        "reads_expired_race",
        "read_p50_ms",
        "read_p99_ms",
        "writes_throttled",
        "writes_rejected",
        "backpressure_ms_mean",
        "lost_rows",
        "duplicated_rows",
        "orphans_removed",
        "leaked_file_count",
    ]
    row = {
        "metric": "traffic soak (3 writers / 2 readers, shared buckets, churning compaction+expiry)",
        "mode": "full (backpressure + resilience)" if full else "seed (no backpressure, no retries)",
        "fault_rate": round(1.0 / possibility, 3) if possibility else 0.0,
        **{k: report.get(k) for k in keep},
    }
    if full:
        # the acceptance gate: a full-stack soak at 5% faults must be clean
        assert report["consistent"], report
        assert report["commits_failed"] == 0, report
        assert report["lost_rows"] == 0 and report["duplicated_rows"] == 0, report
        assert report["leaked_file_count"] == 0, report
        assert report["read_p99_ms"] is not None, report
    return row


def run_proc_mode(mode: str, duration: float, seed: int) -> dict:
    from paimon_tpu.service.proc_soak import DEFAULT_SCRIPTED_KILLS, ProcSoakConfig, run_proc_soak

    full = mode == "proc-full"
    cfg = ProcSoakConfig(
        duration_s=duration,
        writers=2,
        readers=1,
        seed=seed,
        scripted_kills=DEFAULT_SCRIPTED_KILLS,
        kill_period_s=8.0,
        sweep_period_s=12.0,
        resilient=full,
    )
    tmp = tempfile.mkdtemp(prefix=f"paimon_proc_soak_bench_{mode}_")
    try:
        report = run_proc_soak(tmp, cfg)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    keep = [
        "wall_s",
        "consistent",
        "accepted_commits",
        "commits_per_sec",
        "rounds_intended",
        "rounds_landed",
        "rounds_failed",
        "rounds_ack_lost",
        "crash_recoveries",
        "procs_spawned",
        "procs_killed",
        "procs_respawned",
        "sweeps_during_soak",
        "reads_ok",
        "read_errors",
        "lost_rows",
        "duplicated_rows",
        "expected_unique_keys",
        "total_record_count",
        "orphans_removed",
        "leaked_file_count",
    ]
    row = {
        "metric": "process-grain crash soak (2 writer + 1 reader OS processes, kill -9 at crash points + random)",
        "mode": (
            "full (journal recovery + CAS retries + orphan sweep)"
            if full
            else "seed (no retries, no recovery probe, no sweep)"
        ),
        **{k: report.get(k) for k in keep},
    }
    if full:
        # the acceptance gate: >= 5 process kills survived with nothing lost
        assert report["consistent"], report
        assert report["procs_killed"] >= 5, report
        assert report["lost_rows"] == 0 and report["duplicated_rows"] == 0, report
        assert report["read_errors"] == 0, report
        assert report["leaked_file_count"] == 0, report
        assert report["total_record_count"] == report["expected_unique_keys"], report
    else:
        # the contrast gate: the same kill schedule demonstrably loses
        # commits and/or leaks files without the recovery machinery
        assert report["leaked_file_count"] > 0 or report["rounds_failed"] > 0, report
        assert report["crash_recoveries"] == 0, report
    return row


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")  # host-side soak: never grab the chip
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=60.0)
    ap.add_argument("--seed-duration", type=float, default=20.0, help="contrast run length")
    ap.add_argument("--fault-possibility", type=int, default=20, help="1/N ops fail (20 = 5%%)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-process", action="store_true", help="skip the process-grain rows")
    ap.add_argument("--no-thread", action="store_true", help="skip the thread-soak rows")
    args = ap.parse_args()
    rows = []
    modes = []
    if not args.no_thread:
        modes += [("full", args.duration), ("seed", args.seed_duration)]
    if not args.no_process:
        modes += [("proc-full", args.duration), ("proc-seed", args.seed_duration)]
    for mode, dur in modes:
        if mode.startswith("proc"):
            row = run_proc_mode(mode, dur, args.seed)
        else:
            row = run_mode(mode, dur, args.fault_possibility, args.seed)
        rows.append(row)
        print(json.dumps(row))
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results", "soak_bench.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=2)


if __name__ == "__main__":
    from paimon_tpu.utils import enable_compile_cache

    enable_compile_cache()
    main()
