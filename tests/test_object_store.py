"""Object-store FileIO (S3 semantics): conditional-PUT CAS, rename hazards,
flat namespace, and the full table stack + commit protocol over it, including
cross-process races (reference: paimon-filesystems/paimon-s3 +
FileStoreCommitImpl.java:948-957 commit-under-lock-with-exists-check)."""

import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.fs import get_file_io
from paimon_tpu.fs.object_store import ObjectStoreFileIO
from paimon_tpu.types import BIGINT, DOUBLE, RowType

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCHEMA = RowType.of(("k", BIGINT()), ("v", DOUBLE()))


# ---- store semantics ----------------------------------------------------


def test_conditional_put_is_cas(tmp_path):
    io = get_file_io("s3://x")
    p = f"s3://{tmp_path}/obj"
    assert io.try_atomic_write(p, b"first") is True
    assert io.try_atomic_write(p, b"second") is False
    assert io.read_bytes(p) == b"first"
    with pytest.raises(FileExistsError):
        io.write_bytes(p, b"third")  # overwrite=False = conditional PUT
    io.write_bytes(p, b"fourth", overwrite=True)  # plain PUT clobbers
    assert io.read_bytes(p) == b"fourth"


def test_conditional_put_many_racers_one_winner(tmp_path):
    io = get_file_io("s3://x")
    p = f"s3://{tmp_path}/contested"
    wins = []
    barrier = threading.Barrier(8)

    def racer(i):
        barrier.wait()
        if io.try_atomic_write(p, f"racer-{i}".encode()):
            wins.append(i)

    ts = [threading.Thread(target=racer, args=(i,)) for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(wins) == 1
    assert io.read_bytes(p) == f"racer-{wins[0]}".encode()


def test_legacy_store_has_no_exclusive_create(tmp_path):
    io = ObjectStoreFileIO(conditional_put=False)
    p = f"{tmp_path}/obj"
    assert io.try_atomic_write(p, b"a") is True
    assert io.try_atomic_write(p, b"b") is False  # advisory check still works serially
    assert io.atomic_write_supported is False


def test_rename_copies_and_is_not_exclusive(tmp_path):
    """rename = CopyObject + DeleteObject: content lands whole, but the
    destination check is advisory — a commit protocol must not CAS on it."""
    io = get_file_io("s3://x")
    a, b = f"s3://{tmp_path}/a", f"s3://{tmp_path}/b"
    io.write_bytes(a, b"payload")
    assert io.rename(a, b) is True
    assert not io.exists(a) and io.read_bytes(b) == b"payload"
    # dst exists: advisory check refuses (serially)
    io.write_bytes(a, b"other")
    assert io.rename(a, b) is False


def test_flat_namespace(tmp_path):
    io = get_file_io("s3://x")
    io.write_bytes(f"s3://{tmp_path}/pfx/deep/key", b"v")
    assert io.exists(f"s3://{tmp_path}/pfx")  # prefix "exists" via its objects
    io.mkdirs(f"s3://{tmp_path}/whatever")  # no-op, never fails
    names = [s.path for s in io.list_status(f"s3://{tmp_path}/pfx")]
    assert names == [f"{tmp_path}/pfx/deep"]
    assert io.delete(f"s3://{tmp_path}/pfx", recursive=True) is True
    assert not io.exists(f"s3://{tmp_path}/pfx/deep/key")


def test_no_staging_leaks(tmp_path):
    io = get_file_io("s3://x")
    for i in range(5):
        io.write_bytes(f"s3://{tmp_path}/k{i}", b"x" * 100)
        io.try_atomic_write(f"s3://{tmp_path}/k{i}", b"loser")
    staging = tmp_path / ".os-staging"
    assert not staging.exists() or not any(staging.iterdir())


# ---- table stack over the object store ----------------------------------


def _write(t, ks, vs):
    wb = t.new_batch_write_builder()
    w = wb.new_write()
    w.write({"k": np.asarray(ks, dtype=np.int64), "v": np.asarray(vs, dtype=np.float64)})
    wb.new_commit().commit(w.prepare_commit())


def _read(t):
    rb = t.new_read_builder()
    return sorted(rb.new_read().read_all(rb.new_scan().plan()).to_pylist())


def test_table_end_to_end_on_object_store(tmp_path):
    cat = FileSystemCatalog(f"s3://{tmp_path}", commit_user="s3user")
    t = cat.create_table("db.t", SCHEMA, primary_keys=["k"], options={"bucket": "2"})
    _write(t, [1, 2, 3], [1.0, 2.0, 3.0])
    _write(t, [2, 4], [22.0, 4.0])
    assert _read(t) == [(1, 1.0), (2, 22.0), (3, 3.0), (4, 4.0)]
    # commits engaged the catalog lock (no atomic rename on this store)
    assert t.store.new_commit()._lock is not None


def test_table_on_legacy_store_with_jdbc_lock(tmp_path):
    cat = FileSystemCatalog(f"s3-legacy://{tmp_path}/wh", commit_user="legacy")
    t = cat.create_table(
        "db.t",
        SCHEMA,
        primary_keys=["k"],
        options={
            "bucket": "1",
            "commit.catalog-lock.type": "jdbc",
            "commit.catalog-lock.jdbc-path": str(tmp_path / "locks.db"),
        },
    )
    _write(t, [1, 2], [1.0, 2.0])
    _write(t, [1], [11.0])
    assert _read(t) == [(1, 11.0), (2, 2.0)]
    from paimon_tpu.catalog.jdbc import JdbcCatalogLock

    assert isinstance(t.store.new_commit()._lock, JdbcCatalogLock)


# ---- cross-process -------------------------------------------------------


def run_py(code: str, check: bool = True) -> subprocess.CompletedProcess:
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        timeout=180,
        cwd=REPO_ROOT,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": "/root"},
    )
    if check:
        assert r.returncode == 0, r.stderr
    return r


def test_concurrent_committers_across_processes_on_object_store(tmp_path):
    """Two OS processes commit at once on the rename-less store: the catalog
    lock + conditional-PUT CAS must serialize them, keeping both commits."""
    cat = FileSystemCatalog(f"s3://{tmp_path}", commit_user="parent")
    cat.create_table("db.cc", SCHEMA, primary_keys=["k"], options={"bucket": "1"})
    outs = {}

    def worker(name, key):
        outs[name] = run_py(f"""
            import jax; jax.config.update("jax_platforms", "cpu")
            from paimon_tpu.table import load_table
            t = load_table("s3://{tmp_path}/db.db/cc", commit_user="{name}")
            wb = t.new_batch_write_builder(); w = wb.new_write()
            w.write({{"k": [{key}], "v": [{key}.0]}})
            wb.new_commit().commit(w.prepare_commit())
            print("committed")
        """).stdout

    t1 = threading.Thread(target=worker, args=("alice", 1))
    t2 = threading.Thread(target=worker, args=("bob", 2))
    t1.start(); t2.start(); t1.join(); t2.join()
    t = cat.get_table("db.cc")
    assert _read(t) == [(1, 1.0), (2, 2.0)]
    assert t.store.snapshot_manager.latest_snapshot_id() == 2


def test_crashing_committer_process_on_object_store(tmp_path):
    """A separate process crashes mid-commit under fault injection on the
    object store; the table must stay consistent and writable (lock not
    wedged, no partial snapshot)."""
    domain = "oscrash"
    wh = f"fail-s3://{domain}{tmp_path}"
    cat = FileSystemCatalog(f"s3://{tmp_path}", commit_user="parent")
    cat.create_table(
        "db.cr", SCHEMA, primary_keys=["k"],
        options={"bucket": "1", "commit.catalog-lock.acquire-timeout": "15",
                 "commit.catalog-lock.check-max-sleep": "5"},
    )
    # child: crash randomly across many attempted commits, record which
    # identifiers it believes landed
    r = run_py(f"""
        import jax; jax.config.update("jax_platforms", "cpu")
        from paimon_tpu.fs.testing import FailingFileIO, ArtificialException
        from paimon_tpu.table import load_table
        landed = []
        for attempt in range(12):
            FailingFileIO.reset("{domain}", max_fails=2, possibility=3, seed=attempt)
            try:
                t = load_table("{wh}/db.db/cr", commit_user="crashproc")
                wb = t.new_batch_write_builder(); w = wb.new_write()
                w.write({{"k": [attempt], "v": [float(attempt)]}})
                wb.new_commit().commit(w.prepare_commit())
                landed.append(attempt)
            except ArtificialException:
                pass
        FailingFileIO.reset("{domain}", max_fails=0, possibility=0)
        print("landed", landed)
    """)
    landed = eval(r.stdout.split("landed", 1)[1].strip())
    # parent: table is consistent — every snapshot parses, and every key the
    # child saw land is present
    t = cat.get_table("db.cr")
    sm = t.store.snapshot_manager
    for sid in range(1, (sm.latest_snapshot_id() or 0) + 1):
        sm.snapshot(sid)  # parses fully — no partial snapshot ever visible
    got = {r[0] for r in _read(t)}
    assert set(landed) <= got
    # and still writable by the parent afterwards (lock not wedged)
    _write(t, [999], [9.9])
    assert 999 in {r[0] for r in _read(t)}


def test_file_lock_rejected_on_store_without_exclusive_create(tmp_path):
    """s3-legacy + default (file) lock would be check-then-put theater: the
    commit must refuse loudly instead of silently losing commits."""
    cat = FileSystemCatalog(f"s3-legacy://{tmp_path}/wh2", commit_user="x")
    t = cat.create_table("db.bad", SCHEMA, primary_keys=["k"], options={"bucket": "1"})
    with pytest.raises(ValueError, match="jdbc"):
        _write(t, [1], [1.0])


def test_stale_lock_sweep_has_single_deleter(tmp_path):
    """Crashed holder past TTL: racing waiters must serialize via the
    content-keyed sweep tombstone — never two holders at once, and the sweep
    never deletes a fresh lock."""
    import time as _time

    from paimon_tpu.catalog.lock import FileBasedCatalogLock

    io = get_file_io("s3://x")
    base = f"s3://{tmp_path}/tbl"
    io.mkdirs(base)
    # a crashed holder's stale lock
    io.write_bytes(f"{base}/.catalog-lock", f"deadbeef {_time.time() - 999}".encode())
    active = []
    overlaps = []

    def waiter(i):
        lk = FileBasedCatalogLock(io, base, timeout=30.0, stale_ttl=5.0)
        with lk.lock():
            active.append(i)
            if len(active) > 1:
                overlaps.append(list(active))
            _time.sleep(0.05)
            active.remove(i)

    ts = [threading.Thread(target=waiter, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert overlaps == []  # mutual exclusion held through the takeover
    # no tombstone litter
    leftovers = [s.path for s in io.list_status(base) if ".sweep-" in s.path]
    assert leftovers == []
