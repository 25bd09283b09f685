#!/usr/bin/env python
"""Benchmark: commit throughput under injected transient faults.

Runs N small write->commit rounds against a fail:// store at 0% / 1% / 5%
injected transient-fault rates, in two configurations:

  resilient   fs.retry defaults (RetryingFileIO + bounded commit retry)
  seed        fs.retry.max-attempts=1 — the pre-resilience behavior where
              the FIRST fault aborts the commit

Demonstrates graceful degradation: with the resilience layer every commit
succeeds at every rate (bounded slowdown from backoff), while the seed
configuration aborts a commit on nearly every injected fault.

Prints one JSON line per (rate, mode) with commits/s, failed commits, and the
io{retries, giveups} counters. Also writes benchmarks/results/resilience_bench.json.
"""

import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paimon_tpu.core.manifest import ManifestCommittable
from paimon_tpu.core.schema import SchemaManager
from paimon_tpu.core.store import KeyValueFileStore
from paimon_tpu.data import ColumnBatch
from paimon_tpu.fs import get_file_io
from paimon_tpu.fs.testing import ArtificialException, FailingFileIO
from paimon_tpu.metrics import io_metrics, registry
from paimon_tpu.types import BIGINT, DOUBLE, RowType

SCHEMA = RowType.of(("k", BIGINT()), ("v", DOUBLE()))
N_COMMITS = 25
ROWS_PER_COMMIT = 200
RATES = [(0.0, 0), (0.01, 100), (0.05, 20)]  # (rate, 1/possibility)


def run_config(rate: float, possibility: int, resilient: bool, seed: int = 7) -> dict:
    domain = f"bench_{'res' if resilient else 'seed'}_{int(rate * 100)}"
    tmp = tempfile.mkdtemp(prefix="paimon_resilience_bench_")
    try:
        FailingFileIO.reset(domain, 0, 0)
        io = get_file_io(f"fail://{domain}/x")
        path = f"fail://{domain}{tmp}/table"
        opts = {"bucket": "1", "commit.retry-backoff": "2 ms"}
        if resilient:
            opts.update({"fs.retry.initial-backoff": "2 ms", "fs.retry.max-backoff": "50 ms"})
        else:
            opts["fs.retry.max-attempts"] = "1"
        ts = SchemaManager(io, path).create_table(SCHEMA, primary_keys=["k"], options=opts)
        store = KeyValueFileStore(io, path, ts, commit_user="bench")
        registry.reset()
        g = io_metrics()
        rng = np.random.default_rng(seed)
        FailingFileIO.reset(domain, max_fails=10**9, possibility=possibility, seed=seed)
        failed = 0
        committed = 0
        t0 = time.perf_counter()
        for i in range(1, N_COMMITS + 1):
            ks = rng.integers(0, 10_000, ROWS_PER_COMMIT).tolist()
            vs = [float(x) for x in rng.random(ROWS_PER_COMMIT)]
            try:
                w = store.new_writer((), 0)
                w.write(ColumnBatch.from_pydict(store.value_schema, {"k": ks, "v": vs}))
                msg = w.prepare_commit()
                store.new_commit().commit(ManifestCommittable(i, messages=[msg]))
                committed += 1
            except ArtificialException:
                failed += 1  # seed behavior: first fault aborts the commit
        dt = time.perf_counter() - t0
        FailingFileIO.reset(domain, 0, 0)
        return {
            "metric": "commit throughput under injected faults",
            "fault_rate": rate,
            "mode": "resilient" if resilient else "seed",
            "commits": committed,
            "failed_commits": failed,
            "commits_per_sec": round(committed / dt, 2) if dt > 0 else None,
            "io_retries": g.counter("retries").count,
            "io_giveups": g.counter("giveups").count,
            "wall_s": round(dt, 3),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")  # host-side IO bench: never grab the chip
    run_config(0.0, 0, True)  # warm jit/format caches so timings compare configs, not compilation
    rows = []
    for rate, possibility in RATES:
        for resilient in (True, False):
            row = run_config(rate, possibility, resilient)
            rows.append(row)
            print(json.dumps(row))
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results", "resilience_bench.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=2)


if __name__ == "__main__":
    from paimon_tpu.utils import enable_compile_cache

    enable_compile_cache()
    main()
