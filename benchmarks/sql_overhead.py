#!/usr/bin/env python
"""SQL layer overhead on the headline table: SELECT through sql.query vs the
direct Table API, plus a pushdown query and a GROUP BY. Emits one JSON line
per row. The SQL layer should cost noise (<5%) on a full scan — it routes to
the same read path — and the grouped aggregate should run at scan-like rates.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench as B  # repo-root headline-table builder; its import enables the
                   # compile cache and requires the device

PLATFORM = B._PLATFORM
N = B.N_ROWS


def best_of(fn, iters=4):
    best = float("inf")
    out = None
    for i in range(iters + 1):
        t0 = time.perf_counter()
        out = fn()
        dt = time.perf_counter() - t0
        if i:
            best = min(best, dt)
    return best, out


def main():
    from paimon_tpu.catalog import FileSystemCatalog
    from paimon_tpu.sql import query

    tmp = tempfile.mkdtemp(prefix="sql_ovh_")
    try:
        B.build_table(tmp)
        cat = FileSystemCatalog(tmp, commit_user="bench")

        def direct():
            t = cat.get_table("bench.t")
            rb = t.new_read_builder()
            return rb.new_read().read_all(rb.new_scan().plan())

        def via_sql():
            return query(cat, "SELECT * FROM bench.t")

        def pushdown():
            return query(cat, "SELECT id, c1 FROM bench.t WHERE id < 100000")

        def grouped():
            return query(cat, "SELECT s2, count(*), sum(c1) FROM bench.t GROUP BY s2")

        t_direct, out = best_of(direct)
        assert out.num_rows == N
        t_sql, out = best_of(via_sql)
        assert out.num_rows == N
        t_push, out = best_of(pushdown)
        t_group, gout = best_of(grouped)
        assert gout.num_rows == 10  # s2 has 10 distinct values

        rows = [
            ("sql.select-star", N / t_sql, {"overhead_vs_direct": round(t_sql / t_direct - 1, 4)}),
            ("sql.direct-api", N / t_direct, {}),
            ("sql.pushdown-projection", N / t_push, {"selected": out.num_rows}),
            ("sql.group-by-agg", N / t_group, {"groups": gout.num_rows}),
        ]
        for metric, rps, extra in rows:
            print(json.dumps({"metric": metric, "value": round(rps, 1), "unit": "rows/s",
                              "platform": PLATFORM, **extra}), flush=True)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
