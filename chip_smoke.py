#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that paimon-tpu still starts on the chip.

One process drives the system's main path once on a TPU, through the entry
points a user calls (FileSystemCatalog.create_table, the batch write builder
and commit, scan plan / read_all, DedicatedCompactor, sort_compact,
paimon_tpu.sql, service.gateway.Gateway) with DEFAULT table options — no
`sort-engine`, no PAIMON_TPU_FORCE_* / PAIMON_TPU_*_ENGINE in its
environment — and compares every result with a plain numpy reference written
here. Data is made from --seed; the sizes are the constants below — a cut
is an edit to them, recorded in CHANGES.md, never an option.

Every step prints the sort engine it resolved, compilations and compile
seconds, rows in/out, wall seconds, the devices' peak bytes, and its proof
that the device ran it: how far each device's bytes_in_use rose above the
step's start while the step ran (sampled from a thread — a kernel's operands
and results are live for as long as the host waits for it). A device step
whose devices' memory never moved fails; so does a host-only step (the
write of sorted unique runs, sort-engine=numpy) that moved it. Every section
must also have asked XLA for programs — compiled cold, loaded from the
persistent cache warm. Sections:

  load        upstream's micro-benchmark table at TableFormatBenchmark's row
              count (10,000,000 input rows, bench.py's eight-column schema,
              deduplicate, parquet, one bucket, write-only) as 4 sorted runs
              with overlapping key ranges, a fifth of the keys rewritten by a
              later run; merge-read twice (cold, warm)
  queries     projected scan with a two-int-column predicate; GROUP BY
              through paimon_tpu.sql (BIGINT sums past 2^31, an exact DOUBLE
              sum); Gateway.get_batch of 10,000 present and absent keys and
              the same SQL through the gateway; full compaction, read again
  families    partial-update (4 runs), aggregation sum/max over 8 buckets in
              ORC, z-order sort-compact, a SQL equi-join on the device join
  engines     every value `sort-engine` accepts, on a 2M-row table, output
              identical to the default engine
  mesh        with >= 2 TPU devices: 8 buckets x 8M rows read and compacted
              under merge.engine=mesh, bit-identical to the single engine,
              plus __graft_entry__._dryrun_impl on the real devices

It refuses to run without a TPU (exit 1 before anything is built), fails on
the first exception or mismatch, and prints as its last line
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
These are smoke readings, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import shutil
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np

HEADLINE_ROWS = 10_000_000  # upstream TableFormatBenchmark.java:41
FAMILY_ROWS = 2_000_000  # each other kernel family, and the sort-engine copies
MESH_ROWS = 8_000_000  # over 8 buckets, with two or more chips
GATEWAY_KEYS = 10_000  # half present, half absent

_FORBIDDEN_ENV = ("PAIMON_TPU_FORCE_", "PAIMON_TPU_SORT_ENGINE", "PAIMON_TPU_MERGE_ENGINE",
                  "PAIMON_TPU_JOIN_ENGINE", "PAIMON_TPU_DICT_ENGINE", "PAIMON_TPU_ENCODE_ENGINE",
                  "PAIMON_TPU_DECODE_ENGINE")


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def check_equal(what, got, want):
    """Exact equality of two numpy arrays (NaN never appears in this data)."""
    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape, f"{what}: shape {got.shape} != {want.shape}")
    if got.dtype != object and want.dtype != object:
        check(got.dtype == want.dtype, f"{what}: dtype {got.dtype} != {want.dtype}")
    same = np.asarray(got == want)
    if not same.all():
        i = int(np.flatnonzero(~same)[0])
        raise SmokeFailure(f"{what}: first mismatch at row {i}: got {got[i]!r}, want {want[i]!r}")


# ---------------------------------------------------------------------------
# device proof: programs requested, device memory moved
# ---------------------------------------------------------------------------


class DeviceMeter:
    """What this process can see of the device from outside the package: the
    XLA programs it asked for — compiled, or loaded from the persistent
    cache — with the seconds that took, and every device's memory_stats().
    On this runtime `bytes_in_use` and `peak_bytes_in_use` are exact and a
    reading costs 2 us; `num_allocs` is the number of allocations alive NOW
    (it falls on free), so it says nothing about a step whose buffers are
    gone by its end."""

    def __init__(self):
        import jax
        from jax import monitoring

        self.devices = jax.devices()
        self.requests = 0
        self.hits = 0
        self.compile_s = 0.0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.compile_s += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def memory(self, key: str) -> list[int]:
        return [d.memory_stats()[key] for d in self.devices]

    def snapshot(self):
        return {"requests": self.requests, "hits": self.hits, "compile_s": self.compile_s}

    def since(self, before):
        hits = self.hits - before["hits"]
        return {
            "compilations": self.requests - before["requests"] - hits,
            "cache_hits": hits,
            "compile_s": round(self.compile_s - before["compile_s"], 3),
            "peak_device_bytes": self.memory("peak_bytes_in_use"),
        }

    @contextlib.contextmanager
    def bytes_in_use_rise(self):
        """Yields a list that holds, after the body, how far each device's
        bytes_in_use rose above its value at entry. The peak the runtime
        keeps cannot be reset, so a thread reads bytes_in_use every 0.2 ms:
        a kernel's operands and results stay allocated at least while the
        host waits for it, GIL released, which is many readings long."""
        gc.collect()  # garbage of the step before must not count as this one's base
        base = self.memory("bytes_in_use")
        high = list(base)
        stop = threading.Event()
        failed = []

        def poll():
            try:
                while not stop.wait(0.0002):
                    high[:] = map(max, high, self.memory("bytes_in_use"))
            except BaseException as e:  # re-raised below, in the step's thread
                failed.append(e)

        rise: list[int] = []
        t = threading.Thread(target=poll, name="smoke-device-meter", daemon=True)
        t.start()
        try:
            yield rise
        finally:
            stop.set()
            t.join()
        if failed:
            raise failed[0]
        rise[:] = [h - b for h, b in zip(high, base)]


class Smoke:
    def __init__(self, seed: int, tmp: str):
        import jax

        from paimon_tpu.catalog import FileSystemCatalog

        self.jax = jax
        self.seed = seed
        self.cat = FileSystemCatalog(tmp, commit_user="smoke")
        self.meter = DeviceMeter()
        self.platform = jax.devices()[0].platform
        self.steps = 0
        self.engines: set[str] = set()

    def step(self, name, fn, table=None, device=True):
        """Run one step of a section: fn checks its own result against the
        reference and returns what to print; `table` names the merge engine;
        device=False declares a step that must stay on the host."""
        before = self.meter.snapshot()
        with self.meter.bytes_in_use_rise() as rise:
            t0 = time.perf_counter()
            info = fn() or {}
            wall = time.perf_counter() - t0
        row = {"step": name, "platform": self.platform, **self.meter.since(before),
               "device_bytes_in_use_rise": rise, "wall_s": round(wall, 3)}
        if device:
            check(max(rise) > 0, f"{name}: no device's bytes_in_use rose while it ran — the device did not run it")
        else:
            check(max(rise) == 0, f"{name}: a host-only step moved device memory: {rise}")
        if table is not None:
            engine = table.store.merge_executor().effective_sort_engine().value
            asked = info.get("engine_asked")
            check(engine == asked if asked else engine != "numpy",
                  f"{name}: the merge engine resolved to {engine} (asked: {asked or 'the default'})")
            row["engine"] = engine
            self.engines.add(engine)
        row.update(info)
        self.steps += 1
        print("[smoke] " + json.dumps(row), flush=True)

    @contextlib.contextmanager
    def section(self, name):
        """One section of the smoke: the engines its steps resolved and the
        XLA programs requested for it — compiled cold, loaded from the
        persistent cache warm; a section that asked for none ran on the
        host. (Each step carries its own proof, see step.)"""
        before = self.meter.snapshot()
        steps0, t0 = self.steps, time.perf_counter()
        self.engines = set()
        yield
        row = {"section": name, "platform": self.platform, "engines": sorted(self.engines),
               "steps": self.steps - steps0, **self.meter.since(before),
               "wall_s": round(time.perf_counter() - t0, 3)}
        check(row["compilations"] + row["cache_hits"] > 0,
              f"section {name}: no XLA program was compiled or loaded — the device did not run it")
        print("[smoke] " + json.dumps(row), flush=True)


def read_all(table, projection=None, predicate=None):
    rb = table.new_read_builder()
    if projection is not None:
        rb = rb.with_projection(projection)
    if predicate is not None:
        rb = rb.with_filter(predicate)
    return rb.new_read().read_all(rb.new_scan().plan())


def col(batch, name):
    """(values, validity mask) of one output column as numpy."""
    c = batch.column(name)
    return np.asarray(c.values), c.valid_mask()


def commit_run(table, data: dict):
    wb = table.new_batch_write_builder()
    w = wb.new_write()
    w.write(data)
    wb.new_commit().commit(w.prepare_commit())


# ---------------------------------------------------------------------------
# the headline table: bench.py's schema at TableFormatBenchmark's row count
# ---------------------------------------------------------------------------

S1_VOCAB = np.array([f"val-{i:04d}" for i in range(1000)], dtype=object)
S2_VOCAB = np.array([f"tag-{i}" for i in range(10)], dtype=object)


def headline_schema():
    """bench.py's eight-column schema (upstream's micro-benchmark table)."""
    import paimon_tpu as pt

    return pt.RowType.of(
        ("id", pt.BIGINT(False)), ("c1", pt.BIGINT()), ("c2", pt.BIGINT()), ("c3", pt.BIGINT()),
        ("d1", pt.DOUBLE()), ("d2", pt.DOUBLE()), ("s1", pt.STRING()), ("s2", pt.STRING()),
    )


def write_runs(table, runs):
    for r, chunk in enumerate(runs):
        commit_run(table, headline_columns(chunk, np.full(len(chunk), r, dtype=np.int64)))


def headline_columns(ids: np.ndarray, run: np.ndarray) -> dict:
    """Every column is a function of (id, writing run): the reference needs
    only the winners' (id, run). d1 holds halves, so DOUBLE sums are exact in
    any association order yet far outside float32's exact range."""
    return {
        "id": ids,
        "c1": ids * 4 + run,
        "c2": ids % 97,
        "c3": ids // 7,
        "d1": ids.astype(np.float64) * 0.5 + run,
        "d2": ids.astype(np.float64) + 0.25,
        "s1": S1_VOCAB[ids % 1000],
        "s2": S2_VOCAB[ids % 10],
    }


def make_runs(rng, input_rows: int, num_runs: int = 4):
    """num_runs sorted runs over one key universe: every key has a home run,
    a fifth of the keys are rewritten once by a later run. Returns
    [ids per run] and the reference winners (ids ascending, winning run)."""
    distinct = int(round(input_rows / 1.2))
    rewrites = input_rows - distinct
    home = rng.integers(0, num_runs, distinct).astype(np.int64)
    ids = np.arange(distinct, dtype=np.int64) * 3 + 1  # sparse keys: absent ones exist
    candidates = np.flatnonzero(home < num_runs - 1)
    chosen = rng.choice(candidates, size=rewrites, replace=False)
    later = home[chosen] + 1 + (rng.integers(0, 1 << 30, rewrites) % (num_runs - 1 - home[chosen]))
    runs = []
    for r in range(num_runs):
        members = np.concatenate([np.flatnonzero(home == r), chosen[later == r]])
        runs.append(np.sort(ids[members]))
    winner_run = home.copy()
    winner_run[chosen] = later
    return runs, ids, winner_run


def check_headline(out, ids_w, run_w, what, columns=None):
    want = headline_columns(ids_w, run_w)
    check(out.num_rows == len(ids_w), f"{what}: {out.num_rows} rows, want {len(ids_w)}")
    for name in columns or list(want):
        values, valid = col(out, name)
        check(bool(valid.all()), f"{what}: unexpected NULL in {name}")
        check_equal(f"{what}.{name}", values, want[name])


def section_load(s: Smoke):
    input_rows = HEADLINE_ROWS
    rng = np.random.default_rng(s.seed)
    runs, ids_w, run_w = make_runs(rng, input_rows)
    schema = headline_schema()
    table = s.cat.create_table(
        "bench.t", schema, primary_keys=["id"],
        options={"bucket": "1", "file.format": "parquet", "write-only": "true"},
    )
    state = {"table": table, "ids_w": ids_w, "run_w": run_w, "input_rows": input_rows}

    def write():
        write_runs(table, runs)
        return {"rows_in": input_rows, "runs": len(runs), "rows_per_run": [len(c) for c in runs]}

    # sorted unique runs flush without a merge: the write is host work
    s.step("load.write-4-runs", write, table, device=False)

    from paimon_tpu.options import CoreOptions

    tile_rows = table.store.options.options.get(CoreOptions.MERGE_READ_BATCH_ROWS)

    def read(tag):
        def run():
            out = read_all(table)
            check_headline(out, ids_w, run_w, f"load.{tag}")
            return {"rows_in": input_rows, "rows_out": out.num_rows,
                    "merge.read-batch-rows": tile_rows, "tiled_dispatch": input_rows > tile_rows}
        return run

    s.step("load.merge-read-cold", read("cold"), table)
    s.step("load.merge-read-warm", read("warm"), table)
    return state


def section_queries(s: Smoke, st: dict):
    from paimon_tpu.data.predicate import and_, greater_or_equal, less_than
    from paimon_tpu.metrics import sql_metrics
    from paimon_tpu.service.gateway import Gateway
    from paimon_tpu.sql import query
    from paimon_tpu.table.compactor import DedicatedCompactor

    table, ids_w, run_w = st["table"], st["ids_w"], st["run_w"]
    want_all = headline_columns(ids_w, run_w)

    def scan_with_predicate():
        c3_cut = int(ids_w[len(ids_w) // 2] // 7)
        out = read_all(table, projection=["id", "c2", "c3", "d1"],
                       predicate=and_(greater_or_equal("c2", 10), less_than("c3", c3_cut)))
        keep = (want_all["c2"] >= 10) & (want_all["c3"] < c3_cut)
        check(out.num_rows == int(keep.sum()), f"scan: {out.num_rows} rows, want {int(keep.sum())}")
        for name in ("id", "c2", "c3", "d1"):
            check_equal(f"scan.{name}", col(out, name)[0], want_all[name][keep])
        return {"rows_in": st["input_rows"], "rows_out": out.num_rows}

    s.step("queries.projected-scan-2-int-predicates", scan_with_predicate, table)

    grp = ids_w % 10
    want_count = np.bincount(grp, minlength=10).astype(np.int64)
    want_c1 = np.array([want_all["c1"][grp == g].sum() for g in range(10)], dtype=np.int64)
    want_c3 = np.array([want_all["c3"][grp == g].max() for g in range(10)], dtype=np.int64)
    # halves summed in f64: exact in any association order
    want_d1 = np.array([np.sum(want_all["d1"][grp == g]) for g in range(10)], dtype=np.float64)
    check(int(want_c1.min()) > (1 << 31), "reference BIGINT sums do not pass 2^31")

    def by_tag(out):
        tags = col(out, "s2")[0]
        order = np.argsort(tags.astype(str))
        check_equal("group keys", tags[order], S2_VOCAB)
        return order

    sql_double = "SELECT s2, count(*), sum(c1), sum(d1) FROM bench.t GROUP BY s2"
    sql_ints = "SELECT s2, count(*), sum(c1), max(c3) FROM bench.t GROUP BY s2"

    def check_double(out, what):
        order = by_tag(out)
        check_equal(f"{what}.count", col(out, "count(*)")[0][order], want_count)
        check_equal(f"{what}.sum(c1)", col(out, "sum(c1)")[0][order], want_c1)
        check_equal(f"{what}.sum(d1)", col(out, "sum(d1)")[0][order], want_d1)

    def group_by_double():
        reduced0 = sql_metrics().counter("rows_reduced_device").count
        out = query(s.cat, sql_double)
        check_double(out, "sql")
        on_device = sql_metrics().counter("rows_reduced_device").count - reduced0
        # ops/aggregates.segment_reduce: a DOUBLE argument sends the whole
        # reduce to the host on a TPU (f64 is emulated there and not exact);
        # the scan underneath is still the device merge
        return {"rows_out": out.num_rows, "group_reduce": "device" if on_device else "host (f64 on tpu)",
                "sum_c1_min": int(want_c1.min())}

    s.step("queries.sql-group-by-bigint-double", group_by_double, table)

    def group_by_ints():
        reduced0 = sql_metrics().counter("rows_reduced_device").count
        out = query(s.cat, sql_ints)
        order = by_tag(out)
        check_equal("sql.count", col(out, "count(*)")[0][order], want_count)
        check_equal("sql.sum(c1)", col(out, "sum(c1)")[0][order], want_c1)
        check_equal("sql.max(c3)", col(out, "max(c3)")[0][order], want_c3)
        on_device = sql_metrics().counter("rows_reduced_device").count - reduced0
        check(on_device == len(ids_w), f"segment_reduce ran on the device for {on_device} rows, want {len(ids_w)}")
        return {"rows_out": out.num_rows, "group_reduce": "device", "rows_reduced_device": on_device}

    s.step("queries.sql-group-by-device-reduce", group_by_ints, table)

    def gateway():
        rng = np.random.default_rng(s.seed + 1)
        half = GATEWAY_KEYS // 2
        present = rng.choice(ids_w, size=half, replace=False)
        absent = rng.choice(ids_w, size=half, replace=False) + 1  # keys are 1 mod 3
        keys = np.concatenate([present, absent])
        rng.shuffle(keys)
        gw = Gateway(table, catalog=s.cat)
        try:
            got = gw.get_batch([int(k) for k in keys])
            pos = np.searchsorted(ids_w, keys)
            pos_c = np.minimum(pos, len(ids_w) - 1)
            found = ids_w[pos_c] == keys
            check(int(found.sum()) == half, "reference: present/absent split is wrong")
            names = list(want_all)
            for i, row in enumerate(got):
                if not found[i]:
                    check(row is None, f"gateway.get_batch: absent key {int(keys[i])} returned {row!r}")
                    continue
                want = tuple(want_all[n][pos_c[i]] for n in names)
                check(row is not None and tuple(row) == tuple(w.item() if hasattr(w, "item") else w for w in want),
                      f"gateway.get_batch: key {int(keys[i])}: got {row!r}, want {want!r}")
            out = gw.sql(sql_double)
            check_double(out, "gateway.sql")
        finally:
            gw.close()
        return {"keys": len(keys), "present": half, "absent": half, "rows_out": out.num_rows}

    s.step("queries.gateway-get-batch-and-sql", gateway, table)

    def compact_and_read():
        done = DedicatedCompactor(table).run_once(full=True)
        check(done, "full compaction reported nothing to do")
        files = table.store.new_scan().plan().entries
        levels = {e.file.level for e in files}
        check(len(levels) == 1 and 0 not in levels, f"after full compaction files sit on levels {sorted(levels)}")
        out = read_all(table)
        check_headline(out, ids_w, run_w, "after-compaction")
        return {"rows_in": st["input_rows"], "rows_out": out.num_rows, "files_after": len(files)}

    s.step("queries.full-compaction-then-read", compact_and_read, table)


# ---------------------------------------------------------------------------
# the other kernel families, once each through the Table API
# ---------------------------------------------------------------------------


def section_partial_update(s: Smoke):
    """BASELINE config 2's shape: partial-update, 4 runs, each writing a
    different subset of the fields for a random subset of the keys."""
    import paimon_tpu as pt

    rows = FAMILY_ROWS
    per = rows // 4
    keys_n = per + per // 4
    rng = np.random.default_rng(s.seed + 2)
    schema = pt.RowType.of(
        ("id", pt.BIGINT(False)), ("a", pt.BIGINT()), ("b", pt.BIGINT()),
        ("d0", pt.DOUBLE()), ("d1", pt.DOUBLE()), ("s0", pt.STRING()),
    )
    table = s.cat.create_table(
        "smoke.pu", schema, primary_keys=["id"],
        options={"bucket": "1", "merge-engine": "partial-update", "write-only": "true"},
    )
    vocab = np.array([f"v{i}" for i in range(97)], dtype=object)
    writes = {"a": (0, 2), "b": (1, 3), "d0": (0, 1, 2, 3), "d1": (2, 3), "s0": (0, 1, 2, 3)}
    present = np.zeros((4, keys_n), dtype=bool)

    def value(field, ids, r):
        if field == "a":
            return ids % 1000 + r
        if field == "b":
            return ids % 777 + r
        if field == "d0":
            return ids * 0.5 + r
        if field == "d1":
            return ids * 1.5 + r
        return vocab[(ids + r) % 97]

    def run():
        for r in range(4):
            ids = np.sort(rng.choice(keys_n, size=per, replace=False)).astype(np.int64)
            present[r, ids] = True
            data = {"id": ids}
            for field, runs in writes.items():
                data[field] = value(field, ids, r) if r in runs else [None] * per
            commit_run(table, data)
        out = read_all(table)
        all_ids = np.arange(keys_n, dtype=np.int64)
        exists = present.any(axis=0)
        check_equal("partial-update.id", col(out, "id")[0], all_ids[exists])
        for field, runs in writes.items():
            last = np.full(keys_n, -1, dtype=np.int64)
            for r in runs:
                last[present[r]] = r
            want_valid = (last >= 0)[exists]
            values, valid = col(out, field)
            check_equal(f"partial-update.{field}.validity", valid, want_valid)
            ids_e, last_e = all_ids[exists], last[exists]
            want = np.empty(len(ids_e), dtype=values.dtype)
            for r in runs:
                m = last_e == r
                want[m] = value(field, ids_e[m], r)
            check_equal(f"partial-update.{field}", values[want_valid], want[want_valid])
        return {"rows_in": per * 4, "rows_out": out.num_rows}

    s.step("families.partial-update-4-runs", run, table)


def section_aggregation(s: Smoke):
    """BASELINE config 3's shape: aggregation sum/max, ORC, 8 buckets."""
    import paimon_tpu as pt

    rows = FAMILY_ROWS
    per = rows // 4
    keys_n = rows // 8
    rng = np.random.default_rng(s.seed + 3)
    schema = pt.RowType.of(("id", pt.BIGINT(False)), ("sum_col", pt.BIGINT()), ("max_col", pt.DOUBLE()))
    table = s.cat.create_table(
        "smoke.agg", schema, primary_keys=["id"],
        options={
            "bucket": "8", "file.format": "orc", "merge-engine": "aggregation",
            "fields.sum_col.aggregate-function": "sum",
            "fields.max_col.aggregate-function": "max",
            "write-only": "true",
        },
    )

    def run():
        want_sum = np.zeros(keys_n, dtype=np.int64)
        want_max = np.full(keys_n, -np.inf)
        for r in range(4):
            ids = rng.integers(0, keys_n, per).astype(np.int64)
            sums = (ids % 7 + 1) * 600_000_000  # per-key totals pass 2^31
            maxes = ids * 0.25 + rng.integers(0, 1000, per)
            np.add.at(want_sum, ids, sums)
            np.maximum.at(want_max, ids, maxes)
            commit_run(table, {"id": ids, "sum_col": sums, "max_col": maxes})
        out = read_all(table)
        exists = want_sum > 0
        order = np.argsort(col(out, "id")[0], kind="stable")  # 8 buckets: key order is per bucket
        check_equal("aggregation.id", col(out, "id")[0][order], np.flatnonzero(exists).astype(np.int64))
        check_equal("aggregation.sum", col(out, "sum_col")[0][order], want_sum[exists])
        check_equal("aggregation.max", col(out, "max_col")[0][order], want_max[exists])
        check(int(want_sum.max()) > (1 << 31), "reference sums do not pass 2^31")
        return {"rows_in": per * 4, "rows_out": out.num_rows, "buckets": 8, "format": "orc"}

    s.step("families.aggregation-sum-max-8-buckets-orc", run, table)


def _morton(x: np.ndarray, y: np.ndarray, bits: int) -> np.ndarray:
    code = np.zeros(len(x), dtype=np.uint64)
    for b in range(bits):
        code |= ((x >> b) & 1).astype(np.uint64) << np.uint64(2 * b + 1)
        code |= ((y >> b) & 1).astype(np.uint64) << np.uint64(2 * b)
    return code


def section_sort_compact(s: Smoke):
    """BASELINE config 5's clustering half: z-order sort-compact of an
    append table; the reference is a stable sort by the Morton code."""
    import paimon_tpu as pt
    from paimon_tpu.table.sort_compact import sort_compact

    rows = FAMILY_ROWS
    rng = np.random.default_rng(s.seed + 4)
    schema = pt.RowType.of(("id", pt.BIGINT(False)), ("x", pt.BIGINT()), ("y", pt.BIGINT()), ("v", pt.DOUBLE()))
    table = s.cat.create_table("smoke.zorder", schema, primary_keys=[], options={"bucket": "1"})

    def run():
        ids = np.arange(rows, dtype=np.int64)
        x = rng.integers(0, 4096, rows).astype(np.int64)
        y = rng.integers(0, 4096, rows).astype(np.int64)
        commit_run(table, {"id": ids, "x": x, "y": y, "v": ids * 1.0})
        n = sort_compact(table, ["x", "y"], order="zorder")
        check(n == rows, f"sort_compact rewrote {n} rows, want {rows}")
        out = read_all(table)
        perm = np.argsort(_morton(x, y, 12), kind="stable")
        check_equal("zorder.id", col(out, "id")[0], ids[perm])
        check_equal("zorder.x", col(out, "x")[0], x[perm])
        check_equal("zorder.y", col(out, "y")[0], y[perm])
        return {"rows_in": rows, "rows_out": out.num_rows}

    s.step("families.sort-compact-zorder", run, table)


def section_join(s: Smoke):
    """One SQL equi-join big enough for join.engine=auto to take the device
    join; the reference is a searchsorted lookup."""
    import paimon_tpu as pt
    from paimon_tpu.metrics import join_metrics
    from paimon_tpu.ops.join import resolve_join_engine
    from paimon_tpu.sql import query

    rows = FAMILY_ROWS
    dim_n = rows // 20
    rng = np.random.default_rng(s.seed + 5)
    fact = s.cat.create_table(
        "smoke.fact", pt.RowType.of(("id", pt.BIGINT(False)), ("dk", pt.BIGINT()), ("amt", pt.BIGINT())),
        primary_keys=[], options={"bucket": "1"},
    )
    dim = s.cat.create_table(
        "smoke.dim", pt.RowType.of(("dk", pt.BIGINT(False)), ("w", pt.BIGINT())),
        primary_keys=["dk"], options={"bucket": "1"},
    )

    def run():
        ids = np.arange(rows, dtype=np.int64)
        dk = rng.integers(0, dim_n * 2, rows).astype(np.int64)  # half the probes miss
        amt = rng.integers(0, 1_000_000, rows).astype(np.int64)
        commit_run(fact, {"id": ids, "dk": dk, "amt": amt})
        dks = np.arange(0, dim_n * 2, 2, dtype=np.int64)
        commit_run(dim, {"dk": dks, "w": dks * 7 + 3})
        engine = resolve_join_engine(fact.store.options, rows=rows + dim_n)
        check(engine == "xla", f"join.engine=auto resolved to {engine!r}, want the device join")
        joins0 = join_metrics().counter("joins").count
        out = query(s.cat, "SELECT f.id, f.amt, d.w FROM smoke.fact f JOIN smoke.dim d ON f.dk = d.dk")
        check(join_metrics().counter("joins").count == joins0 + 1, "the SQL join did not go through join_batches")
        hit = dk % 2 == 0
        order = np.argsort(col(out, "id")[0], kind="stable")
        check_equal("join.id", col(out, "id")[0][order], ids[hit])
        check_equal("join.amt", col(out, "amt")[0][order], amt[hit])
        check_equal("join.w", col(out, "w")[0][order], dk[hit] * 7 + 3)
        return {"rows_in": rows + dim_n, "rows_out": out.num_rows, "join_engine": engine}

    s.step("families.sql-equi-join-device", run, fact)


# ---------------------------------------------------------------------------
# every sort-engine value, on a 2M-row copy
# ---------------------------------------------------------------------------


def section_engines(s: Smoke):
    from paimon_tpu.options import SortEngine

    rows = FAMILY_ROWS
    rng = np.random.default_rng(s.seed + 6)
    runs, ids_w, run_w = make_runs(rng, rows)
    schema = headline_schema()
    table = s.cat.create_table(
        "smoke.engines", schema, primary_keys=["id"],
        options={"bucket": "1", "file.format": "parquet", "write-only": "true"},
    )
    write_runs(table, runs)
    baseline = {}

    def default_engine():
        out = read_all(table)
        check_headline(out, ids_w, run_w, "engines.default")
        baseline["out"] = out
        return {"rows_in": rows, "rows_out": out.num_rows}

    s.step("engines.default", default_engine, table)

    for engine in SortEngine:
        copy = table.copy({"sort-engine": engine.value})

        def run(engine=engine, copy=copy):
            out = read_all(copy)
            for name in schema.field_names:
                check_equal(f"engines.{engine.value}.{name}", col(out, name)[0], col(baseline["out"], name)[0])
            return {"engine_asked": engine.value, "rows_in": rows, "rows_out": out.num_rows}

        # numpy is the host oracle engine by definition: it must not touch the device
        s.step(f"engines.sort-engine={engine.value}", run, copy, device=engine != SortEngine.NUMPY)


# ---------------------------------------------------------------------------
# two or more chips: the mesh engine
# ---------------------------------------------------------------------------


def section_mesh(s: Smoke):
    from paimon_tpu.metrics import mesh_metrics
    from paimon_tpu.table.compactor import DedicatedCompactor

    n_dev = len(s.jax.devices())
    rows = MESH_ROWS
    rng = np.random.default_rng(s.seed + 7)
    runs, ids_w, run_w = make_runs(rng, rows)
    schema = headline_schema()
    opts = {"bucket": "8", "file.format": "parquet", "write-only": "true"}
    single = s.cat.create_table("smoke.mesh_single", schema, primary_keys=["id"], options=opts)
    mesh = s.cat.create_table("smoke.mesh", schema, primary_keys=["id"],
                              options=dict(opts, **{"merge.engine": "mesh"}))
    write_runs(single, runs)
    write_runs(mesh, runs)
    outs = {}

    def read(table, tag):
        def run():
            g = mesh_metrics()
            shards0, sharded0 = g.counter("shards").count, g.counter("buckets_sharded").count
            peaks0 = s.meter.memory("peak_bytes_in_use")
            out = read_all(table)
            order = np.argsort(col(out, "id")[0], kind="stable")
            check_equal(f"mesh.{tag}.id", col(out, "id")[0][order], ids_w)
            outs[tag] = out
            info = {"rows_in": rows, "rows_out": out.num_rows, "buckets": 8}
            if tag.startswith("mesh"):
                shards = g.counter("shards").count - shards0
                sharded = g.counter("buckets_sharded").count - sharded0
                check(shards > 0 and sharded >= 8,
                      f"MeshExecutor ran {shards} shard_map batches over {sharded} buckets")
                # the single-engine steps before this one touched device 0
                # only: every other device's peak must have grown here
                growth = [a - a0 for a, a0 in zip(s.meter.memory("peak_bytes_in_use"), peaks0)]
                check(all(g > 0 for g in growth[1:]),
                      f"operands were not sharded over all devices: peak-byte growth per device {growth}")
                info.update(shard_map_batches=shards, buckets_sharded=sharded, peak_growth_per_device=growth)
            return info
        return run

    s.step("mesh.single-engine-read", read(single, "single"), single)
    s.step("mesh.mesh-engine-read", read(mesh, "mesh"), mesh)
    for name in schema.field_names:  # bit-identical, row for row
        check_equal(f"mesh-vs-single.{name}", col(outs["mesh"], name)[0], col(outs["single"], name)[0])

    def compact(table, tag):
        def run():
            g = mesh_metrics()
            shards0 = g.counter("shards").count
            check(DedicatedCompactor(table).run_once(full=True), f"{tag}: nothing compacted")
            out = read_all(table)
            outs[tag + ".compacted"] = out
            info = {"rows_in": rows, "rows_out": out.num_rows}
            if tag == "mesh":
                info["shard_map_batches"] = g.counter("shards").count - shards0
                check(info["shard_map_batches"] > 0, "mesh compaction ran no shard_map batch")
            return info
        return run

    s.step("mesh.single-engine-full-compaction", compact(single, "single"), single)
    s.step("mesh.mesh-engine-full-compaction", compact(mesh, "mesh"), mesh)
    for name in schema.field_names:
        check_equal(f"mesh-vs-single.compacted.{name}",
                    col(outs["mesh.compacted"], name)[0], col(outs["single.compacted"], name)[0])
    order = np.argsort(col(outs["mesh.compacted"], "id")[0], kind="stable")
    for name, want in headline_columns(ids_w, run_w).items():
        check_equal(f"mesh.compacted.{name}", col(outs["mesh.compacted"], name)[0][order], want)

    def dryrun():
        import __graft_entry__ as graft

        graft._dryrun_impl(n_dev)
        return {"devices": n_dev}

    s.step("mesh.dryrun-distributed-steps", dryrun)


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    bad = sorted(k for k in os.environ if k.startswith(_FORBIDDEN_ENV))
    if bad:
        print(f"[smoke] refusing to run with engine overrides in the environment: {bad}", file=sys.stderr)
        return 1

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"[smoke] no TPU: jax.devices()[0].platform == {platform!r}; nothing was built", file=sys.stderr)
        return 1

    import jaxlib

    import paimon_tpu  # noqa: F401  (turns on x64 before any array exists)
    from paimon_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not importable"
    device = {"platform": platform, "kind": devices[0].device_kind, "count": len(devices)}
    print("[smoke] " + json.dumps({
        "device": device, "jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu_version,
        "x64": bool(jax.config.jax_enable_x64), "compile_cache": cache_dir,
        "cache_entries_at_start": len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0,
        "seed": args.seed,
        "sizes": {"headline_rows": HEADLINE_ROWS, "family_rows": FAMILY_ROWS, "mesh_rows": MESH_ROWS,
                  "gateway_keys": GATEWAY_KEYS},
    }), flush=True)

    tmp = tempfile.mkdtemp(prefix="paimon_tpu_smoke_")
    t0 = time.perf_counter()
    try:
        s = Smoke(args.seed, tmp)
        start = s.meter.snapshot()
        with s.section("load"):
            st = section_load(s)
        with s.section("queries"):
            section_queries(s, st)
        del st
        with s.section("families"):
            section_partial_update(s)
            section_aggregation(s)
            section_sort_compact(s)
            section_join(s)
        with s.section("engines"):
            section_engines(s)
        if len(devices) >= 2:
            with s.section("mesh"):
                section_mesh(s)
        else:
            print("[smoke] mesh: NOT RUN — one TPU device visible; the mesh engine needs two or more "
                  "and this script does not fake devices", flush=True)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print("[smoke] not exercised: the JAX twins in paimon_tpu/decode/kernels.py and "
          "paimon_tpu/encode/kernels.py are not routed by any table path today (numpy is)", flush=True)
    print("[smoke] " + json.dumps({"total": True, "steps": s.steps, **s.meter.since(start),
                                   "wall_s": round(time.perf_counter() - t0, 1)}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
