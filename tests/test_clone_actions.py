"""Clone pipeline + round-3 action parity (clone, compact_database,
reset_consumer, expire_partitions, drop_partition, mark_partition_done).

Reference: flink/clone/{CloneSourceBuilder,PickFilesUtil,CopyFileOperator,
SnapshotHintOperator}.java, action/{CloneAction,CompactDatabaseAction,
ResetConsumerAction,ExpirePartitionsAction,DropPartitionAction,
MarkPartitionDoneAction}.java."""

import datetime
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.table import clone as C
from paimon_tpu.types import BIGINT, DOUBLE, STRING, RowType

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCHEMA = RowType.of(("id", BIGINT()), ("v", DOUBLE()), ("s", STRING()))


def run_cli(*argv):
    r = subprocess.run(
        [sys.executable, "-m", "paimon_tpu", *argv],
        capture_output=True, text=True, timeout=180, cwd=REPO_ROOT,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": "/root",
             "JAX_ENABLE_X64": "true"},
    )
    assert r.returncode == 0, r.stderr
    return r.stdout.strip()


def _write(t, lo, hi, tag=None):
    wb = t.new_batch_write_builder()
    w = wb.new_write()
    ids = np.arange(lo, hi, dtype=np.int64)
    w.write({"id": ids, "v": ids * 0.5, "s": np.array([f"s{i}" for i in ids], dtype=object)})
    wb.new_commit().commit(w.prepare_commit())
    if tag:
        t.create_tag(tag)


@pytest.fixture
def src(tmp_path):
    cat = FileSystemCatalog(str(tmp_path / "src"), commit_user="setup")
    t = cat.create_table("db.t", SCHEMA, primary_keys=["id"], options={"bucket": "2"})
    _write(t, 0, 100, tag="v1")
    _write(t, 50, 150)  # overlap: exercises merge + multiple manifests
    return cat, t


def _read_ids(t):
    rb = t.new_read_builder()
    out = rb.new_read().read_all(rb.new_scan().plan())
    return sorted(r[0] for r in out.to_pylist())


def test_clone_table_latest(src, tmp_path):
    cat, t = src
    dst_cat = FileSystemCatalog(str(tmp_path / "dst"), commit_user="clone")
    cloned = C.clone_table(t, dst_cat, "mirror.t2")
    assert _read_ids(cloned) == list(range(150))
    # cloned table is independently writable
    _write(cloned, 200, 210)
    assert len(_read_ids(cloned)) == 160
    assert len(_read_ids(t)) == 150  # source untouched


def test_clone_tag_and_branch(src, tmp_path):
    cat, t = src
    dst_cat = FileSystemCatalog(str(tmp_path / "dst"), commit_user="clone")
    from paimon_tpu.table.tags import TagManager

    sid = TagManager(t.file_io, t.path).snapshot_id("v1")
    cloned = C.clone_table(t, dst_cat, "mirror.tagged", snapshot_id=sid)
    assert _read_ids(cloned) == list(range(100))  # pre-second-write state

    from paimon_tpu.table.branch import BranchManager, branch_table

    BranchManager(t.file_io, t.path).create("b1", from_tag="v1")
    bt = branch_table(t, "b1")
    _write(bt, 1000, 1010)
    cloned_b = C.clone_table(bt, dst_cat, "mirror.branched")
    assert _read_ids(cloned_b) == list(range(100)) + list(range(1000, 1010))


def test_clone_database_cli(src, tmp_path):
    cat, t = src
    t2 = cat.create_table("db.u", SCHEMA, primary_keys=["id"], options={"bucket": "1"})
    _write(t2, 0, 10)
    out = json.loads(run_cli(
        "clone", "--warehouse", str(tmp_path / "src"), "--database", "db",
        "--target-warehouse", str(tmp_path / "dst2"), "--target-database", "copy",
    ))
    assert sorted(out["cloned"]) == ["copy.t", "copy.u"]
    dst = FileSystemCatalog(str(tmp_path / "dst2"))
    assert _read_ids(dst.get_table("copy.u")) == list(range(10))


def test_clone_preserves_changelog(tmp_path):
    """The changelog manifests + files ride along (CopyFileOperator copies
    the full snapshot closure); a changelog scan on the clone works."""
    cat = FileSystemCatalog(str(tmp_path / "src"), commit_user="setup")
    t = cat.create_table("db.cl", SCHEMA, primary_keys=["id"],
                         options={"bucket": "1", "changelog-producer": "input"})
    _write(t, 0, 10)
    _write(t, 5, 15)
    dst_cat = FileSystemCatalog(str(tmp_path / "dst"), commit_user="clone")
    cloned = C.clone_table(t, dst_cat, "mirror.cl")
    rb = cloned.new_read_builder()
    scan = rb.new_streaming_scan() if hasattr(rb, "new_streaming_scan") else None
    # changelog files referenced by the cloned snapshot must exist
    snap = cloned.store.snapshot_manager.latest_snapshot()
    assert snap.changelog_manifest_list
    from paimon_tpu.core.manifest import ManifestFile, ManifestList

    ml = ManifestList(cloned.file_io, f"{cloned.path}/manifest")
    mf = ManifestFile(cloned.file_io, f"{cloned.path}/manifest")
    n_files = 0
    for meta in ml.read(snap.changelog_manifest_list):
        for e in mf.read(meta.file_name):
            base = cloned.store.bucket_dir(e.partition, e.bucket)
            assert cloned.file_io.exists(f"{base}/{e.file.file_name}")
            n_files += 1
    assert n_files > 0

    # idempotent: a second clone of the same snapshot succeeds
    C.clone_table(t, dst_cat, "mirror.cl")


def test_compact_database_cli(tmp_path):
    wh = str(tmp_path / "wh")
    cat = FileSystemCatalog(wh, commit_user="setup")
    for name in ("db1.a", "db1.b", "db2.c"):
        t = cat.create_table(name, SCHEMA, primary_keys=["id"],
                             options={"bucket": "1", "write-only": "true"})
        _write(t, 0, 20)
        _write(t, 10, 30)
    out = json.loads(run_cli(
        "compact-database", "--warehouse", wh,
        "--including-databases", "db1", "--excluding-tables", "b", "--full",
    ))
    assert out["compacted"] == ["db1.a"]
    # compaction merged the overlapping runs but preserved the data
    assert _read_ids(cat.get_table("db1.a")) == list(range(30))


def test_reset_consumer_cli(src, tmp_path):
    cat, t = src
    from paimon_tpu.table.consumer import ConsumerManager

    cm = ConsumerManager(t.file_io, t.path)
    cm.record("job7", 2)
    base = ["--warehouse", str(tmp_path / "src"), "--table", "db.t"]
    out = json.loads(run_cli("reset-consumer", *base, "--consumer-id", "job7", "--next-snapshot", "1"))
    assert out == {"consumer": "job7", "next_snapshot": 1}
    assert cm.consumer("job7") == 1
    json.loads(run_cli("reset-consumer", *base, "--consumer-id", "job7"))
    assert cm.consumer("job7") is None


@pytest.fixture
def part_table(tmp_path):
    cat = FileSystemCatalog(str(tmp_path / "pw"), commit_user="setup")
    schema = RowType.of(("dt", STRING(False)), ("id", BIGINT()), ("v", DOUBLE()))
    t = cat.create_table("db.p", schema, primary_keys=["dt", "id"],
                         partition_keys=["dt"], options={"bucket": "1"})
    old = (datetime.date.today() - datetime.timedelta(days=30)).isoformat()
    new = datetime.date.today().isoformat()
    for dt in (old, new):
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        w.write({"dt": np.array([dt] * 5, dtype=object),
                 "id": np.arange(5, dtype=np.int64),
                 "v": np.arange(5, dtype=np.float64)})
        wb.new_commit().commit(w.prepare_commit())
    return str(tmp_path / "pw"), t, old, new


def test_expire_partitions_cli(part_table):
    wh, t, old, new = part_table
    out = json.loads(run_cli(
        "expire-partitions", "--warehouse", wh, "--table", "db.p",
        "--expiration-time-hours", str(7 * 24), "--timestamp-formatter", "%Y-%m-%d",
    ))
    assert out["expired_partitions"] == [[old]]
    rb = t.new_read_builder()
    rows = rb.new_read().read_all(rb.new_scan().plan()).to_pylist()
    assert {r[0] for r in rows} == {new}


def test_drop_partition_and_mark_done_cli(part_table):
    wh, t, old, new = part_table
    out = json.loads(run_cli(
        "drop-partition", "--warehouse", wh, "--table", "db.p",
        "--partition", f"dt={old}",
    ))
    assert out["dropped_partitions"] == [[old]]
    rb = t.new_read_builder()
    rows = rb.new_read().read_all(rb.new_scan().plan()).to_pylist()
    assert {r[0] for r in rows} == {new}

    out = json.loads(run_cli(
        "mark-partition-done", "--warehouse", wh, "--table", "db.p",
        "--partition", f"dt={new}",
    ))
    assert len(out["markers"]) == 1
    marker = json.loads(t.file_io.read_bytes(out["markers"][0]))
    assert marker["creationTime"] <= marker["modificationTime"]


def test_query_service_cli(src, tmp_path):
    """query-service action serves lookups over TCP; the client resolves the
    address from the table's service registry (reference QueryService)."""
    import subprocess as sp
    import sys as _sys
    import time

    cat, t = src
    proc = sp.Popen(
        [_sys.executable, "-m", "paimon_tpu", "query-service",
         "--warehouse", str(tmp_path / "src"), "--table", "db.t"],
        stdout=sp.PIPE, stderr=sp.PIPE, text=True, cwd=REPO_ROOT,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": "/root"},
    )
    try:
        line = proc.stdout.readline()
        info = json.loads(line)
        assert info["service"] == "kv-query" and info["port"] > 0
        from paimon_tpu.service import KvQueryClient

        deadline = time.monotonic() + 10
        client = None
        while True:
            try:
                client = KvQueryClient(info["host"], info["port"])
                if client.ping():
                    break
            except OSError:
                pass
            assert time.monotonic() < deadline, "service never became reachable"
            time.sleep(0.2)
        row = client.lookup((), (42,))
        assert row is not None and row[0] == 42
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_clone_under_fault_injection(tmp_path):
    """Clone's retry-on-vanish loop (reference PickFilesUtil.retryReadingFiles)
    survives injected read failures: each failed attempt re-picks from the
    current latest snapshot; once the fault budget is spent the copy lands
    complete and correct."""
    from paimon_tpu.fs.testing import FailingFileIO

    cat = FileSystemCatalog(str(tmp_path / "src"), commit_user="setup")
    t = cat.create_table("db.ft", SCHEMA, primary_keys=["id"], options={"bucket": "2"})
    _write(t, 0, 200)
    _write(t, 100, 300)

    FailingFileIO.reset("clonefault", max_fails=5, possibility=30, seed=3)
    faulty = FileSystemCatalog(f"fail://clonefault{tmp_path}/src", commit_user="setup")
    ft = faulty.get_table("db.ft")
    dst_cat = FileSystemCatalog(str(tmp_path / "dst"), commit_user="clone")
    cloned = C.clone_table(ft, dst_cat, "mirror.ft", parallelism=2, max_retries=10)
    assert _read_ids(cloned) == list(range(300))


def test_repair_cli(tmp_path):
    """repair re-syncs the JDBC metadata plane with the warehouse filesystem
    (reference RepairAction): unregistered on-disk tables get rows, rows
    without backing storage are dropped."""
    from paimon_tpu.catalog.jdbc import JdbcCatalog

    wh = str(tmp_path / "wh")
    db_path = str(tmp_path / "meta.db")
    jcat = JdbcCatalog(db_path, wh, commit_user="setup")
    jcat.create_table("db.keep", SCHEMA, primary_keys=["id"], options={"bucket": "1"})
    jcat.create_table("db.ghost", SCHEMA, primary_keys=["id"], options={"bucket": "1"})
    # a table created OUTSIDE the jdbc catalog (e.g. by the FS catalog)
    fcat = FileSystemCatalog(wh, commit_user="setup")
    t = fcat.create_table("db.orphaned", SCHEMA, primary_keys=["id"], options={"bucket": "1"})
    _write(t, 0, 5)
    # ghost's storage vanishes
    import shutil

    shutil.rmtree(f"{wh}/db.db/ghost")
    out = json.loads(run_cli("repair", "--warehouse", wh, "--jdbc-path", db_path))
    assert out == {"registered": ["db.orphaned"], "removed": ["db.ghost"], "removed_databases": []}
    assert sorted(jcat.list_tables("db")) == ["keep", "orphaned"]
    assert _read_ids(jcat.get_table("db.orphaned")) == list(range(5))

    # a renamed table survives repair: identity is the stored LOCATION, not
    # the naming convention (rename keeps the original path)
    jcat.rename_table("db.keep", "db.kept2")
    out = json.loads(run_cli("repair", "--warehouse", wh, "--jdbc-path", db_path))
    assert out == {"registered": [], "removed": [], "removed_databases": []}
    assert "kept2" in jcat.list_tables("db") and "keep" not in jcat.list_tables("db")


def test_migrate_database_cli(tmp_path):
    """migrate-database: one table per source subdirectory (reference
    MigrateDatabaseAction)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    src = tmp_path / "lake"
    for name in ("orders", "users"):
        (src / name).mkdir(parents=True)
        pq.write_table(
            pa.table({"id": pa.array([1, 2, 3], pa.int64()), "v": pa.array([1.0, 2.0, 3.0])}),
            src / name / "part-0.parquet",
        )
    wh = str(tmp_path / "wh")
    out = json.loads(run_cli(
        "migrate-database", "--warehouse", wh, "--database", "lakehouse",
        "--source-dir", str(src),
    ))
    assert out["migrated"] == ["lakehouse.orders", "lakehouse.users"]
    cat = FileSystemCatalog(wh)
    assert _read_ids(cat.get_table("lakehouse.users")) == [1, 2, 3]
