#!/usr/bin/env python
"""CDC subscription fan-out benchmark: decode-once at 1/8/32/128 subscribers.

The tentpole claim of the subscription service (service/subscription.py) is
that ONE tailer decodes each changelog snapshot exactly once and fans the
same decoded batches out to N subscribers — so decode work is flat in N and
aggregate delivered rows/s scales with N instead of dividing by it.

Two measured sides per subscriber count:

* **hub fan-out** — N subscribers on one SubscriptionHub follow a live
  writer streaming commits into a fresh table: the tailer decodes + merges
  each snapshot once and fans the shared batch to every queue. Reported:
  aggregate delivered rows/s (all subscribers, commit start -> last
  delivery), per-subscriber p99 delivery lag (commit -> batch handed to
  that subscriber), and the decode{pages_decoded} delta — asserted FLAT in
  N (the decode-once proof; the table reads through the native decoder so
  every decoded page counts).

* **independent scans** (baseline at N=32) — N independent StreamTableScan
  loops, each decoding for itself with the shared data-file cache disabled
  on its handle: the faithful model of N separate consumer processes, which
  cannot share decoded batches. Headline: hub aggregate rows/s >= 5x the
  independent aggregate at 32 subscribers.

Results land in benchmarks/results/subscribe_bench.json; bench.py runs
run_headline() for its spot-check row.
"""

import json
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_COMMITS = 16
ROWS_PER_COMMIT = 4_000
SUBSCRIBER_COUNTS = (1, 8, 32, 128)
BASELINE_N = 32
TARGET_SPEEDUP = 5.0


def _schema():
    import paimon_tpu as pt

    return pt.RowType.of(
        ("k", pt.BIGINT(False)),
        ("cat", pt.STRING()),  # low-cardinality: dictionary-encoded pages
        ("v", pt.DOUBLE()),
    )


def build_table(base: str, name: str):
    from paimon_tpu.catalog import FileSystemCatalog

    cat = FileSystemCatalog(base, commit_user="subbench")
    t = cat.create_table(
        f"db.{name}",
        _schema(),
        primary_keys=["k"],
        options={
            "bucket": "2",
            # every decoded page must count: the native decoder feeds
            # decode{pages_decoded}, which the flatness assertion reads
            "format.parquet.decoder": "native",
            "format.parquet.encoder": "native",
            "subscription.queue-depth": "64",
            "subscription.poll-backoff": "5 ms",
        },
    )
    return t


def stream_commits(table, commit_times: dict[int, float] | None = None, lock=None):
    """Write N_COMMITS commits of ROWS_PER_COMMIT rows, recording each landed
    append snapshot's commit time for lag measurement."""
    rng = np.random.default_rng(7)
    cats = np.array(["alpha", "beta", "gamma", "delta"], dtype=object)
    wb = table.new_batch_write_builder()
    for c in range(N_COMMITS):
        w = wb.new_write()
        keys = (np.arange(ROWS_PER_COMMIT, dtype=np.int64) + c * ROWS_PER_COMMIT).tolist()
        w.write(
            {
                "k": keys,
                "cat": cats[rng.integers(0, len(cats), ROWS_PER_COMMIT)].tolist(),
                "v": rng.random(ROWS_PER_COMMIT).tolist(),
            }
        )
        sids = wb.new_commit().commit(w.prepare_commit())
        if commit_times is not None:
            with lock:
                for sid in sids:
                    commit_times[sid] = time.perf_counter()


def _pages_decoded() -> int:
    from paimon_tpu.metrics import decode_metrics

    return decode_metrics().counter("pages_decoded").count


def _clear_data_file_cache() -> None:
    from paimon_tpu.utils.cache import data_file_cache

    data_file_cache().clear()


def _append_sids(table) -> set:
    from paimon_tpu.core.snapshot import CommitKind

    sm = table.store.snapshot_manager
    latest = sm.latest_snapshot_id() or 0
    return {
        i
        for i in range(1, latest + 1)
        if sm.snapshot_exists(i) and sm.snapshot(i).commit_kind == CommitKind.APPEND
    }


def run_hub(base: str, n_subs: int) -> dict:
    """N subscribers on a FRESH table with N_COMMITS of preloaded history:

    * throughput phase — every subscriber replays the history through the
      hub (decode + merge happen once; the replay cache and the live queue
      fan the shared batches out). Aggregate rows/s = total delivered rows /
      wall until every subscriber holds every APPEND snapshot.
    * lag phase (not counted in throughput) — a writer streams N_LIVE small
      commits; per-subscriber delivery lag (commit -> handed batch) is
      sampled across all subscribers.
    """
    from paimon_tpu.service.subscription import SubscriptionHub

    N_LIVE = 8
    table = build_table(base, f"hub{n_subs}")
    stream_commits(table)  # preloaded history (not timed)
    _clear_data_file_cache()
    pages0 = _pages_decoded()
    hub = SubscriptionHub(table.with_user("subbench-hub"))
    rows_delivered = [0] * n_subs
    received_sids: list[set] = [set() for _ in range(n_subs)]
    lags_ms: list[float] = []
    commit_times: dict[int, float] = {}
    commit_lock = threading.Lock()
    stop = threading.Event()
    lag_lock = threading.Lock()

    def consume(i: int, sub):
        while True:
            try:
                b = sub.poll(timeout=0.3)
            except Exception:
                break
            if b is None:
                if stop.is_set():
                    break
                continue
            rows_delivered[i] += b.num_rows
            received_sids[i].add(b.snapshot_id)
            with commit_lock:
                t0 = commit_times.get(b.snapshot_id)
            if t0 is not None:
                with lag_lock:
                    lags_ms.append((time.perf_counter() - t0) * 1000)

    history_sids = _append_sids(table)
    subs = [hub.subscribe(consumer_id=f"bench-{n_subs}-{i}", from_snapshot=1) for i in range(n_subs)]
    t_start = time.perf_counter()
    threads = [threading.Thread(target=consume, args=(i, s)) for i, s in enumerate(subs)]
    for th in threads:
        th.start()
    # throughput phase: wait until every subscriber replayed all history
    deadline = time.perf_counter() + 120.0
    while time.perf_counter() < deadline:
        if all(history_sids <= s for s in received_sids):
            break
        time.sleep(0.02)
    wall = time.perf_counter() - t_start
    agg_rows = sum(rows_delivered)
    # lag phase: a live writer streams small commits through the tailer
    wb = table.new_batch_write_builder()
    k = (N_COMMITS + 1) * ROWS_PER_COMMIT
    for _ in range(N_LIVE):
        w = wb.new_write()
        w.write({"k": list(range(k, k + 500)), "cat": ["alpha"] * 500, "v": [0.5] * 500})
        sids = wb.new_commit().commit(w.prepare_commit())
        with commit_lock:
            for sid in sids:
                commit_times[sid] = time.perf_counter()
        k += 500
        time.sleep(0.05)
    expected_sids = _append_sids(table)
    deadline = time.perf_counter() + 60.0
    while time.perf_counter() < deadline:
        if all(expected_sids <= s for s in received_sids):
            break
        time.sleep(0.05)
    stop.set()
    for th in threads:
        th.join(timeout=30.0)
    for s in subs:
        s.close()
    hub.close()
    for i, sids in enumerate(received_sids):
        assert expected_sids <= sids, (
            f"subscriber {i} of {n_subs} missed snapshots: "
            f"{sorted(expected_sids - sids)[:5]}"
        )
    pages = _pages_decoded() - pages0
    return {
        "subscribers": n_subs,
        "wall_s": round(wall, 3),
        "rows_delivered": agg_rows,
        "agg_rows_per_sec": round(agg_rows / wall, 1),
        "live_commits": N_LIVE,
        "snapshots": int(table.store.snapshot_manager.latest_snapshot_id()),
        "pages_decoded": pages,
        "_table": table,
        "lag_p50_ms": round(float(np.percentile(lags_ms, 50)), 2) if lags_ms else None,
        "lag_p99_ms": round(float(np.percentile(lags_ms, 99)), 2) if lags_ms else None,
    }


def run_independent(table, n_subs: int) -> dict:
    """Baseline: N independent StreamTableScan loops, data-file cache OFF on
    their handles (N separate consumer processes cannot share decoded
    batches). Each loop reads the same history for itself."""
    _clear_data_file_cache()
    pages0 = _pages_decoded()
    # cache opt-out on the reader handles only: 0-budget tables skip the
    # process-wide cache entirely (utils/cache.table_caches contract)
    reader_table = table.copy({"cache.data-file.max-memory-size": "0 b"})
    latest = table.store.snapshot_manager.latest_snapshot_id()
    rows_read = [0] * n_subs
    errors: list[str] = []

    def scan_loop(i: int):
        try:
            t = reader_table.with_user(f"indep-{i}")
            scan = t.new_read_builder().new_stream_scan()
            read = t.new_read_builder().new_read()
            scan.restore(1)
            while scan._next is not None and scan._next <= latest:
                splits = scan.plan()
                if splits is None:
                    break
                for s in splits:
                    data, _kinds = read.read_with_kinds(s)
                    rows_read[i] += data.num_rows
        except Exception as exc:  # pragma: no cover - surfaced in the report
            errors.append(f"loop {i}: {exc!r}")

    t_start = time.perf_counter()
    threads = [threading.Thread(target=scan_loop, args=(i,)) for i in range(n_subs)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall = time.perf_counter() - t_start
    assert not errors, errors
    agg = sum(rows_read)
    return {
        "subscribers": n_subs,
        "wall_s": round(wall, 3),
        "rows_delivered": agg,
        "agg_rows_per_sec": round(agg / wall, 1),
        "pages_decoded": _pages_decoded() - pages0,
    }


def run_headline(iters: int = 1) -> list:
    """bench.py spot-check: hub at 32 vs independent at 32 + the flatness
    counters at 1 and 32 (the dedicated sweep runs via main())."""
    base = tempfile.mkdtemp(prefix="subscribe_bench_")
    try:
        hub1 = run_hub(base, 1)
        hub32 = run_hub(base, 32)
        indep = run_independent(hub32.pop("_table"), BASELINE_N)
        hub1.pop("_table", None)
        speedup = hub32["agg_rows_per_sec"] / max(indep["agg_rows_per_sec"], 1e-9)
        return [
            {
                "metric": "subscription fan-out (32 subscribers, decode-once hub vs independent scans)",
                "hub_rows_per_sec": hub32["agg_rows_per_sec"],
                "independent_rows_per_sec": indep["agg_rows_per_sec"],
                "speedup": round(speedup, 2),
                "pages_decoded_1_sub": hub1["pages_decoded"],
                "pages_decoded_32_subs": hub32["pages_decoded"],
                "lag_p99_ms_32_subs": hub32["lag_p99_ms"],
                "shed_subscribers": 0,
                "unit": "rows/s",
            }
        ]
    finally:
        shutil.rmtree(base, ignore_errors=True)


def main() -> int:
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results", "subscribe_bench.json")
    base = tempfile.mkdtemp(prefix="subscribe_bench_")
    results = {"config": {
        "commits": N_COMMITS,
        "rows_per_commit": ROWS_PER_COMMIT,
        "subscriber_counts": list(SUBSCRIBER_COUNTS),
        "baseline_subscribers": BASELINE_N,
    }}
    try:
        sweep = []
        baseline_table = None
        for n in SUBSCRIBER_COUNTS:
            row = run_hub(base, n)
            t = row.pop("_table")
            if n == BASELINE_N:
                baseline_table = t
            print(json.dumps(row))
            sweep.append(row)
        results["hub"] = sweep
        indep = run_independent(baseline_table, BASELINE_N)
        print(json.dumps(dict(indep, mode="independent")))
        results["independent"] = indep
        hub32 = next(r for r in sweep if r["subscribers"] == BASELINE_N)
        speedup = hub32["agg_rows_per_sec"] / max(indep["agg_rows_per_sec"], 1e-9)
        # decode-once proof: pages decoded must NOT scale with N. The live
        # phase writes a few extra snapshots per run, so allow small drift —
        # anything near-linear in N (128x) fails loudly.
        pages = {r["subscribers"]: r["pages_decoded"] for r in sweep}
        flat = max(pages.values()) <= 3 * max(min(pages.values()), 1)
        results["headline"] = {
            "speedup_at_32": round(speedup, 2),
            "target": TARGET_SPEEDUP,
            "pages_decoded_by_n": pages,
            "decode_once_flat": flat,
        }
        print(json.dumps(results["headline"]))
        assert flat, f"pages_decoded scales with subscriber count: {pages}"
        assert speedup >= TARGET_SPEEDUP, (
            f"hub fan-out speedup {speedup:.2f}x below the {TARGET_SPEEDUP}x target"
        )
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(results, f, indent=2)
        print(f"results -> {out_path}")
        return 0
    finally:
        shutil.rmtree(base, ignore_errors=True)


if __name__ == "__main__":
    from paimon_tpu.utils import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
