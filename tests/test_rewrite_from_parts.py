"""A dedup rewrite takes its winners from the per-file parts.

Where the merge picks its winners from the key columns, seq and kind alone
(MergeExecutor.supports_keys_only_pipeline), a rewrite's read head joins only
those (`compact.join`) and the gather takes each value column straight from
the decoded parts, a column a task on the shared pool (mergefn.gather_winners,
the read path's gather). Every rewrite mode must then write exactly the files
of the whole-batch merge: here each case builds the same table twice, once as
the program runs and once with the whole-batch path forced for every rewrite
(the join of every column and merge_async: the sequence the program ran
before), and compares the live files, their contents, the rows read back and
the full-compaction changelog, with the compaction{...} counters each path
gives. The whole-batch engines take the join either way.
"""

import threading
import time

import numpy as np
import pytest

import paimon_tpu as pt
from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.core import compact, mergefn
from paimon_tpu.core import read as core_read
from paimon_tpu.core.mergefn import MergeExecutor
from paimon_tpu.data.predicate import less_than
from paimon_tpu.metrics import registry
from paimon_tpu.utils import SHARED_POOL_THREAD_PREFIX

SCHEMA = pt.RowType.of(("id", pt.BIGINT(False)), ("c", pt.BIGINT()), ("d", pt.DOUBLE()), ("s", pt.STRING()),
                       ("created", pt.BIGINT()))
ARRAYS = len(SCHEMA.fields) + 2  # the columns, seq and kind
NOW = int(time.time())


def _write(t, rng, ids, r, deletes=False, created=None, w=None):
    """One commit of the rows `ids` (sorted, distinct) of writing run `r`:
    nulls in every nullable column, a tenth deleted where asked."""
    commit = w is None
    if commit:
        wb = t.new_batch_write_builder()
        w = wb.new_write()
    null = lambda values: [None if rng.random() < 0.2 else v for v in values]  # noqa: E731
    w.write({"id": ids, "c": null((ids * 10 + r).tolist()), "d": null((ids / 7 + r).tolist()),
             "s": null([f"s{i % 13}-{r}" for i in ids.tolist()]),
             "created": [NOW - 60 * r] * len(ids) if created is None else created},
            kinds=["-D" if deletes and rng.random() < 0.1 else "+I" for _ in ids])
    if commit:
        wb.new_commit().commit(w.prepare_commit())


def _two_ranges(t, rng, deletes=True, expire=False):
    """Four runs in two key ranges: two overlapping runs below 1,000 and two
    above 5,000, so a bucket's full compaction rewrites two sections of two
    runs each. With `expire`, every row of the second run is two hours old
    and a fifth run below 1,000 follows: that section still merges, with an
    empty part between two others."""
    ranges = [(0, 1000), (0, 1000), (5000, 6000), (5000, 6000)] + ([(0, 1000)] if expire else [])
    for r, (lo, hi) in enumerate(ranges):
        ids = np.sort(rng.choice(np.arange(lo, hi), 400, replace=False)).astype(np.int64)
        created = [NOW - 7200] * len(ids) if expire and r == 1 else None
        _write(t, rng, ids, r, deletes=deletes and r % 2 == 1, created=created)


def _concurrent(t, rng):
    """Two writers opened on the same snapshot write one key range each
    commit: their files' sequence ranges overlap, so the runs do not follow
    one another by sequence (order_runs_for_merge: seq_ascending False)."""
    for r in range(2):
        wb = t.new_batch_write_builder()
        writers = [wb.new_write(), wb.new_write()]
        for i, w in enumerate(writers):
            ids = np.sort(rng.choice(2000, 500, replace=False)).astype(np.int64)
            _write(t, rng, ids, 2 * r + i, deletes=i == 1, w=w)
        for w in writers:
            wb.new_commit().commit(w.prepare_commit())


def _one_run(t, rng):
    _write(t, rng, np.arange(0, 3000, 3, dtype=np.int64), 0)


def _full(t):
    """The dedicated compaction job: a write-only table's writers never compact."""
    from paimon_tpu.table.compactor import DedicatedCompactor

    assert DedicatedCompactor(t).run_once(full=True) is True


def _universal(t, rng):
    """Seven commits over one key range at trigger 3: the writer's universal
    compaction rewrites runs while it writes."""
    for r in range(7):
        ids = np.sort(rng.choice(3000, 300, replace=False)).astype(np.int64)
        _write(t, rng, ids, r, deletes=r > 0)


WRITE_ONLY = {"bucket": "1", "write-only": "true"}
UNIVERSAL = {"bucket": "1", "num-sorted-run.compaction-trigger": "3"}

# case -> (options, how the table is written, then compacted, whether the gather from parts engages)
CASES = {
    "full-pipelined": (WRITE_ONLY, _two_ranges, _full, True),
    "full-dispatch-complete": ({**WRITE_ONLY, "scan.prefetch-splits": "0"}, _two_ranges, _full, True),
    "full-mesh": ({"bucket": "4", "write-only": "true", "merge.engine": "mesh"}, _two_ranges, _full, True),
    "universal": (UNIVERSAL, _universal, None, True),
    "universal-mesh": ({**UNIVERSAL, "merge.engine": "mesh"}, _universal, None, True),
    "deletion-vectors": ({**WRITE_ONLY, "deletion-vectors.enabled": "true"},
                         lambda t, rng: (_two_ranges(t, rng), t.delete_where(less_than("id", 300))), _full, True),
    "record-expiry-and-an-empty-part": ({**WRITE_ONLY, "record-level.expire-time.ms": "3600000",
                                         "record-level.time-field": "created"},
                                        lambda t, rng: _two_ranges(t, rng, expire=True), _full, True),
    "full-compaction-changelog": ({**WRITE_ONLY, "changelog-producer": "full-compaction"}, _two_ranges, _full, True),
    "overlapping-sequence-ranges": (WRITE_ONLY, _concurrent, _full, True),
    "sorted-keys-shortcut": (WRITE_ONLY, _one_run, _full, False),
    "code-backed-strings": ({**WRITE_ONLY, "file.format": "parquet", "merge.dict-domain": "true"}, _two_ranges, _full,
                            True),
    "orc": ({**WRITE_ONLY, "file.format": "orc"}, _two_ranges, _full, True),
    # the engines that merge whole batches: the join either way
    "partial-update": ({**WRITE_ONLY, "merge-engine": "partial-update",
                        "partial-update.remove-record-on-delete": "true"}, _two_ranges, _full, False),
    "aggregation": ({**WRITE_ONLY, "merge-engine": "aggregation", "fields.c.aggregate-function": "sum"},
                    lambda t, rng: _two_ranges(t, rng, deletes=False), _full, False),
    "ignore-delete": ({**WRITE_ONLY, "ignore-delete": "true"}, _two_ranges, _full, False),
    "sequence-field": ({**WRITE_ONLY, "sequence.field": "created"}, _two_ranges, _full, False),
}


def _buckets(t):
    return int(t.store.options.bucket)


def _files(t):
    """Every live data file of the table, bucket by bucket in key order: its
    meta but for the name and the clock, and its decoded contents."""
    store = t.store
    out = []
    for b in range(_buckets(t)):
        factory = store.reader_factory((), b)
        for f in sorted(store.restore_files((), b), key=lambda f: (f.level, f.min_key)):
            meta = {k: v for k, v in f.to_dict().items() if k not in ("fileName", "creationTimeMillis", "extraFiles")}
            kv = factory.read(f)
            out.append((b, meta, kv.data.to_pylist(), kv.seq.tolist(), kv.kind.tolist()))
    return out


def _rows(t):
    rb = t.new_read_builder()
    return sorted(rb.new_read().read_all(rb.new_scan().plan()).to_pylist())


def _build(tmp, name, case, whole: bool, monkeypatch):
    """The case's table, written and compacted; with `whole`, every rewrite
    takes the whole-batch path. Returns the table, the compaction{...}
    counters the build moved, the changelog batches the rewrites made and
    the seq_ascending of every section read."""
    options, write, compacting, _ = CASES[case]
    catalog = FileSystemCatalog(str(tmp / name), commit_user="parts")
    t = catalog.create_table("db.t", SCHEMA, primary_keys=["id"],
                             options={"cache.data-file.max-memory-size": "0 b", **options})
    changelog, ascending = [], []
    real_changelog, real_order = compact.MergeTreeCompactRewriter._section_changelog, core_read.order_runs_for_merge

    def section_changelog(self, old_top, merged):
        cl = real_changelog(self, old_top, merged)
        changelog.append((cl.data.to_pylist(), cl.seq.tolist(), cl.kind.tolist()))
        return cl

    def order_runs_for_merge(section):
        runs, seq_ascending = real_order(section)
        ascending.append(seq_ascending)
        return runs, seq_ascending

    with monkeypatch.context() as m:
        m.setattr(compact.MergeTreeCompactRewriter, "_section_changelog", section_changelog)
        m.setattr(core_read, "order_runs_for_merge", order_runs_for_merge)
        if whole:
            m.setattr(MergeExecutor, "supports_keys_only_pipeline", lambda self: False)
        before = dict(registry.snapshot().get("compaction", {}))
        write(t, np.random.default_rng(17))
        if compacting is not None:
            compacting(t)
        after = registry.snapshot().get("compaction", {})
    counted = {k: after.get(k, 0) - before.get(k, 0) for k in ("rows_gathered", "rows_gathered_from_parts", "rows_out")}
    return t, counted, changelog, ascending


@pytest.mark.parametrize("case", CASES)
def test_a_rewrite_from_parts_writes_the_files_of_the_whole_batch_merge(case, tmp_path, monkeypatch):
    if "mesh" in case and len(__import__("jax").devices()) < 2:
        pytest.skip("the mesh engine needs two devices")
    parts, counted, changelog, ascending = _build(tmp_path, "parts", case, False, monkeypatch)
    whole, counted_whole, changelog_whole, _ = _build(tmp_path, "whole", case, True, monkeypatch)
    files = _files(parts)
    assert files == _files(whole)
    assert _rows(parts) == _rows(whole)
    assert changelog == changelog_whole
    assert counted["rows_out"] == counted_whole["rows_out"] > 0
    assert any(meta["level"] == 5 for _, meta, *_ in files) or case.startswith("universal")
    if case == "full-compaction-changelog":
        assert changelog and all(len(rows) for rows, _, _ in changelog)
    assert (False in ascending) == (case == "overlapping-sequence-ranges")
    # every section's winners, every array, on both paths: the same count
    assert counted["rows_gathered"] == counted_whole["rows_gathered"] > 0
    assert counted["rows_gathered"] % ARRAYS == 0 and counted_whole["rows_gathered_from_parts"] == 0
    engages = CASES[case][3]
    # from the parts: the numeric value columns c, d and created (and s where it is code-backed); never the
    # arrow-backed s, which pyarrow's take joins inside, nor the key column, seq and kind
    from_parts = 4 if case == "code-backed-strings" else 3
    assert counted["rows_gathered_from_parts"] == (from_parts * counted["rows_gathered"] // ARRAYS if engages else 0)


def test_a_rewrite_opens_gather_with_a_column_a_task_on_the_pool(tmp_path, monkeypatch):
    seen = []
    real_span = mergefn.span

    def span(name, **stats):
        seen.append((name, threading.current_thread().name, stats))
        return real_span(name, **stats)

    monkeypatch.setattr(mergefn, "span", span)
    t, counted, _, _ = _build(tmp_path, "spans", "full-pipelined", False, monkeypatch)
    gathers = [(thread, stats) for name, thread, stats in seen if name == "gather"]
    assert len(gathers) == 2 and all(stats["parts"] == 2 and stats["columns"] == 5 for _, stats in gathers)
    assert sum(name == "gather.plan" for name, _, _ in seen) == 2
    columns = [(thread, stats) for name, thread, stats in seen if name == "gather.column"]
    assert sorted({s["column"] for _, s in columns}) == sorted(["_kind", "_seq", "id", "c", "d", "s", "created"])
    assert {s["column"]: s["parts"] for _, s in columns} == {"_kind": 1, "_seq": 1, "id": 1, "c": 2, "d": 2, "s": 2,
                                                             "created": 2}
    # kind on the rewrite's thread, the other columns tasks of the shared pool
    assert {thread for thread, s in columns if s["column"] == "_kind"} == {gathers[0][0]}
    assert all(thread.startswith(SHARED_POOL_THREAD_PREFIX) for thread, s in columns if s["column"] != "_kind")
    assert sum(s["rows_out"] for _, s in gathers) * ARRAYS == counted["rows_gathered"]


def test_the_whole_batch_engines_keep_the_join_and_merge_async(tmp_path, monkeypatch):
    calls = []
    real = MergeExecutor.merge_parts_async

    def merge_parts_async(self, *args, **kwargs):
        calls.append(self.engine)
        return real(self, *args, **kwargs)

    monkeypatch.setattr(MergeExecutor, "merge_parts_async", merge_parts_async)
    for case in ("partial-update", "aggregation", "ignore-delete", "sequence-field"):
        _build(tmp_path, case, case, False, monkeypatch)
    assert calls == []
    _build(tmp_path, "dedup", "full-pipelined", False, monkeypatch)
    assert len(calls) == 2  # a section a call
