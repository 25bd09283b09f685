"""Randomized whole-store oracle test: a long random sequence of upserts,
deletes, compactions, expiry, and time travel must always agree with a plain
python dict replay (mirrors the reference's randomized table read-write
suites in paimon-core/src/test/.../table/)."""

import numpy as np
import pytest

from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.types import BIGINT, DOUBLE, STRING, RowType

SCHEMA = RowType.of(("k", BIGINT()), ("s", STRING()), ("v", DOUBLE()))


@pytest.mark.parametrize("seed", [7, 21])
def test_random_ops_match_dict_oracle(tmp_warehouse, seed):
    rng = np.random.default_rng(seed)
    cat = FileSystemCatalog(f"{tmp_warehouse}/{seed}", commit_user="oracle")
    t = cat.create_table(
        "db.r",
        SCHEMA,
        primary_keys=["k"],
        options={
            "bucket": "2",
            "num-sorted-run.compaction-trigger": "3",
            "target-file-size": "4 kb",
        },
    )
    oracle: dict[int, tuple] = {}
    history: list[dict] = []  # snapshot of oracle after each commit

    def do_commit(rows, deletes):
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        if rows:
            ks = [r[0] for r in rows]
            w.write({"k": ks, "s": [r[1] for r in rows], "v": [r[2] for r in rows]})
            for r in rows:
                oracle[r[0]] = (r[0], r[1], r[2])
        if deletes:
            w.write(
                {"k": deletes, "s": [None] * len(deletes), "v": [None] * len(deletes)},
                kinds=["-D"] * len(deletes),
            )
            for k in deletes:
                oracle.pop(k, None)
        if rng.random() < 0.2:
            w.compact(full=rng.random() < 0.5)
        wb.new_commit().commit(w.prepare_commit())
        history.append(dict(oracle))

    for step in range(14):
        n = int(rng.integers(1, 60))
        keys = rng.integers(0, 120, n)
        rows = [(int(k), f"s{int(k)}-{step}", float(step) + float(k) / 1000) for k in keys]
        # dedupe within the batch: later occurrence wins (matches upsert order)
        uniq = {}
        for r in rows:
            uniq[r[0]] = r
        deletes = [int(k) for k in rng.choice(list(oracle), size=min(len(oracle), 5), replace=False)] if oracle and rng.random() < 0.5 else []
        uniq = {k: v for k, v in uniq.items() if k not in deletes}
        do_commit(list(uniq.values()), deletes)

        rb = t.new_read_builder()
        got = {r[0]: r for r in rb.new_read().read_all(rb.new_scan().plan()).to_pylist()}
        assert got == oracle, f"divergence at step {step}"

    # time travel back through every committed snapshot (APPEND ones advance
    # the logical state; COMPACT snapshots in between must not change it)
    sm = t.store.snapshot_manager
    logical = 0
    for snap in sm.snapshots():
        tt = t.copy({"scan.snapshot-id": str(snap.id)})
        rb = tt.new_read_builder()
        got = {r[0]: r for r in rb.new_read().read_all(rb.new_scan().plan()).to_pylist()}
        if snap.commit_kind.value == "APPEND":
            logical += 1
        assert got == history[logical - 1], f"time travel divergence at snapshot {snap.id}"


@pytest.mark.parametrize("seed", [3])
def test_random_ops_cache_parity(tmp_warehouse, seed):
    """Byte-budget caches must be invisible to semantics: the same randomized
    churn (upserts, deletes, compactions, snapshot expiry) read through a
    cache-enabled handle and a cache-disabled handle of ONE physical table
    must always agree with each other and with the dict oracle — including
    right after expire/compaction invalidation."""
    rng = np.random.default_rng(seed)
    cat = FileSystemCatalog(f"{tmp_warehouse}/cachepar{seed}", commit_user="oracle")
    t = cat.create_table(
        "db.cp",
        SCHEMA,
        primary_keys=["k"],
        options={
            "bucket": "2",
            "num-sorted-run.compaction-trigger": "3",
            "target-file-size": "4 kb",
            "manifest.merge-min-count": "2",
            "snapshot.num-retained.min": "2",
            "snapshot.num-retained.max": "4",
            "snapshot.time-retained": "0 ms",
            "cache.manifest.max-memory-size": "64 mb",
            "cache.data-file.max-memory-size": "64 mb",
        },
    )
    # cache-disabled view of the same physical table: ground truth from disk
    plain = t.copy(
        {"cache.manifest.max-memory-size": "0 b", "cache.data-file.max-memory-size": "0 b"}
    )
    oracle: dict[int, tuple] = {}
    for step in range(12):
        n = int(rng.integers(1, 50))
        keys = rng.integers(0, 100, n)
        rows = {}
        for k in keys:
            rows[int(k)] = (int(k), f"s{int(k)}-{step}", float(step) + float(k) / 1000)
        deletes = (
            [int(k) for k in rng.choice(list(oracle), size=min(len(oracle), 4), replace=False)]
            if oracle and rng.random() < 0.4
            else []
        )
        rows = {k: v for k, v in rows.items() if k not in deletes}
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        if rows:
            w.write(
                {
                    "k": [v[0] for v in rows.values()],
                    "s": [v[1] for v in rows.values()],
                    "v": [v[2] for v in rows.values()],
                }
            )
        if deletes:
            w.write(
                {"k": deletes, "s": [None] * len(deletes), "v": [None] * len(deletes)},
                kinds=["-D"] * len(deletes),
            )
        if rng.random() < 0.3:
            w.compact(full=rng.random() < 0.5)
        wb.new_commit().commit(w.prepare_commit())
        oracle.update(rows)
        for k in deletes:
            oracle.pop(k, None)

        def read_dict(table):
            rb = table.new_read_builder()
            return {r[0]: r for r in rb.new_read().read_all(rb.new_scan().plan()).to_pylist()}

        got_cached = read_dict(t)
        got_plain = read_dict(plain)
        assert got_cached == got_plain == oracle, f"cache parity divergence at step {step}"


def test_random_ops_partitioned_dynamic_bucket(tmp_warehouse):
    """Combined paths: partitions + dynamic buckets + deletes + compactions
    against the dict oracle."""
    rng = np.random.default_rng(5)
    cat = FileSystemCatalog(f"{tmp_warehouse}/pdyn", commit_user="oracle2")
    schema = RowType.of(("region", STRING()), ("k", BIGINT()), ("v", DOUBLE()))
    t = cat.create_table(
        "db.p",
        schema,
        partition_keys=["region"],
        primary_keys=["region", "k"],
        options={"bucket": "-1", "dynamic-bucket.target-row-num": "40", "num-sorted-run.compaction-trigger": "3"},
    )
    regions = ["eu", "us", "ap"]
    oracle: dict[tuple, tuple] = {}
    for step in range(10):
        n = int(rng.integers(1, 50))
        ks = rng.integers(0, 150, n)
        rs = [regions[i] for i in rng.integers(0, 3, n)]
        rows = {}
        for r, k in zip(rs, ks):
            rows[(r, int(k))] = (r, int(k), float(step))
        if oracle and rng.random() < 0.5:
            keys = list(oracle)
            idx = rng.choice(len(keys), size=min(len(keys), 4), replace=False)
            deletes = [keys[i] for i in idx]  # sample indices: no key coercion
        else:
            deletes = []
        rows = {key: v for key, v in rows.items() if key not in deletes}
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        if rows:
            w.write(
                {
                    "region": [v[0] for v in rows.values()],
                    "k": [v[1] for v in rows.values()],
                    "v": [v[2] for v in rows.values()],
                }
            )
        if deletes:
            w.write(
                {"region": [d[0] for d in deletes], "k": [d[1] for d in deletes], "v": [None] * len(deletes)},
                kinds=["-D"] * len(deletes),
            )
        if rng.random() < 0.3:
            w.compact(full=True)
        wb.new_commit().commit(w.prepare_commit())
        oracle.update(rows)
        for d in deletes:
            oracle.pop(d, None)
        rb = t.new_read_builder()
        got = {(r[0], r[1]): r for r in rb.new_read().read_all(rb.new_scan().plan()).to_pylist()}
        assert got == oracle, f"divergence at step {step}"


@pytest.mark.skipif(
    __import__("jax").device_count() < 8, reason="needs the 8-device virtual mesh"
)
@pytest.mark.parametrize("seed", [13])
def test_random_ops_mesh_mode_matches_oracle(tmp_warehouse, seed):
    """The same randomized churn with merge.engine=mesh + avro manifests:
    the mesh execution path and the interop metadata plane must be invisible
    to semantics."""
    rng = np.random.default_rng(seed)
    cat = FileSystemCatalog(f"{tmp_warehouse}/mesh{seed}", commit_user="oracle")
    t = cat.create_table(
        "db.rm",
        SCHEMA,
        primary_keys=["k"],
        options={
            "bucket": "4",
            "num-sorted-run.compaction-trigger": "3",
            "target-file-size": "4 kb",
            "merge.engine": "mesh",
            "manifest.format": "avro",
        },
    )
    oracle: dict[int, tuple] = {}
    for step in range(25):
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        n = int(rng.integers(5, 60))
        ks = rng.integers(0, 150, n).tolist()
        rows = [(k, f"s{k % 13}", float(step * 1000 + k)) for k in ks]
        w.write({"k": [r[0] for r in rows], "s": [r[1] for r in rows], "v": [r[2] for r in rows]})
        for r in rows:
            oracle[r[0]] = r
        if rng.random() < 0.3 and oracle:
            idx = rng.integers(0, len(oracle), size=min(5, len(oracle)))
            dels = [sorted(oracle)[i] for i in np.unique(idx)]
            w.write({"k": dels, "s": [None] * len(dels), "v": [None] * len(dels)}, kinds=["-D"] * len(dels))
            for k in dels:
                oracle.pop(k, None)
        if rng.random() < 0.25:
            w.compact(full=rng.random() < 0.4)
        wb.new_commit().commit(w.prepare_commit())
        if step % 6 == 5:
            rb = t.new_read_builder()
            got = {r[0]: r for r in rb.new_read().read_all(rb.new_scan().plan()).to_pylist()}
            assert got == oracle, f"divergence at step {step}"
    rb = t.new_read_builder()
    got = {r[0]: r for r in rb.new_read().read_all(rb.new_scan().plan()).to_pylist()}
    assert got == oracle
