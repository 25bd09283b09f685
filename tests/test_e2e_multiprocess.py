"""Tier-5 analog: real multi-process isolation (the reference's MiniCluster /
docker e2e stands in for this — here separate OS processes share only the
filesystem, proving snapshot isolation and the commit protocol across
process boundaries)."""

import os
import subprocess
import sys
import textwrap

import pytest

from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.types import BIGINT, DOUBLE, RowType

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCHEMA = RowType.of(("k", BIGINT()), ("v", DOUBLE()))


def run_py(code: str) -> str:
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        timeout=120,
        cwd=REPO_ROOT,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": "/root"},
    )
    assert r.returncode == 0, r.stderr
    return r.stdout


def test_writer_process_reader_process(tmp_warehouse):
    cat = FileSystemCatalog(tmp_warehouse, commit_user="parent")
    cat.create_table("db.xs", SCHEMA, primary_keys=["k"], options={"bucket": "1"})
    # a separate OS process writes two commits
    run_py(f"""
        import jax; jax.config.update("jax_platforms", "cpu")
        from paimon_tpu.table import load_table
        t = load_table("{tmp_warehouse}/db.db/xs", commit_user="writerproc")
        for ident, (k, v) in enumerate([(1, 1.0), (1, 11.0)], start=1):
            wb = t.new_batch_write_builder(); w = wb.new_write()
            w.write({{"k": [k], "v": [v]}})
            wb.new_commit().commit(w.prepare_commit())
        print("wrote")
    """)
    # the parent process observes the committed state through the snapshots
    t = cat.get_table("db.xs")
    rb = t.new_read_builder()
    assert rb.new_read().read_all(rb.new_scan().plan()).to_pylist() == [(1, 11.0)]
    assert t.store.snapshot_manager.latest_snapshot().commit_user == "writerproc"


def test_concurrent_committers_across_processes(tmp_warehouse):
    """Two processes commit simultaneously; the CAS loop must keep both."""
    import threading

    cat = FileSystemCatalog(tmp_warehouse, commit_user="parent")
    cat.create_table("db.cc", SCHEMA, primary_keys=["k"], options={"bucket": "1"})
    outs = {}

    def worker(name, key):
        outs[name] = run_py(f"""
            import jax; jax.config.update("jax_platforms", "cpu")
            from paimon_tpu.table import load_table
            t = load_table("{tmp_warehouse}/db.db/cc", commit_user="{name}")
            wb = t.new_batch_write_builder(); w = wb.new_write()
            w.write({{"k": [{key}], "v": [{key}.0]}})
            ids = wb.new_commit().commit(w.prepare_commit())
            print("committed", ids)
        """)

    t1 = threading.Thread(target=worker, args=("alice", 1))
    t2 = threading.Thread(target=worker, args=("bob", 2))
    t1.start(); t2.start(); t1.join(); t2.join()
    t = cat.get_table("db.cc")
    rb = t.new_read_builder()
    out = rb.new_read().read_all(rb.new_scan().plan())
    assert sorted(r[0] for r in out.to_pylist()) == [1, 2]
    assert t.store.snapshot_manager.latest_snapshot_id() == 2


def test_cas_race_shared_bucket_across_processes(tmp_warehouse):
    """Two processes fire ROUNDS commits each into the SAME bucket through
    the real snapshot-CAS retry path, released together by a go-file
    barrier so the rounds genuinely collide. Exactly one committer wins
    each CAS round; every loser's auto-retry must land its commit against
    the new latest — and land it exactly once (no double-applied ADDs)."""
    import os
    import threading

    cat = FileSystemCatalog(tmp_warehouse, commit_user="parent")
    cat.create_table(
        "db.race",
        SCHEMA,
        primary_keys=["k"],
        options={
            "bucket": "1",
            "commit.max-retries": "100",
            "commit.retry-backoff": "2 ms",
            # APPEND-only commits: auto compaction would add COMPACT
            # snapshots (and compact-vs-compact conflicts) — the thread/proc
            # soaks own that storm; this test isolates the snapshot-CAS race
            "write-only": "true",
        },
    )
    ROUNDS = 6
    go = f"{tmp_warehouse}/go"
    outs = {}

    def worker(name, base):
        outs[name] = run_py(f"""
            import jax; jax.config.update("jax_platforms", "cpu")
            import os, time
            from paimon_tpu.core.manifest import ManifestCommittable
            from paimon_tpu.table import load_table
            from paimon_tpu.table.write import TableWrite
            t = load_table("{tmp_warehouse}/db.db/race", commit_user="{name}")
            while not os.path.exists("{go}"):
                time.sleep(0.005)
            sids = []
            for ident in range(1, {ROUNDS} + 1):
                tw = TableWrite(t)
                try:
                    tw.write({{"k": [{base} + ident], "v": [float(ident)]}})
                    msgs = tw.prepare_commit()
                finally:
                    tw.close()
                sids += t.store.new_commit().commit(ManifestCommittable(ident, messages=msgs))
            print("SIDS", ",".join(map(str, sids)))
        """)

    t1 = threading.Thread(target=worker, args=("alice", 1000))
    t2 = threading.Thread(target=worker, args=("bob", 2000))
    t1.start(); t2.start()
    with open(go, "w") as f:
        f.write("go")
    t1.join(); t2.join()

    won = {}
    for name in ("alice", "bob"):
        line = next(ln for ln in outs[name].splitlines() if ln.startswith("SIDS"))
        won[name] = [int(s) for s in line.split(" ", 1)[1].split(",")]
        assert len(won[name]) == ROUNDS  # every round landed despite the races
    # exactly one winner per snapshot id: the two processes' landed ids are
    # disjoint and together cover the chain with no gap and no double
    assert set(won["alice"]).isdisjoint(won["bob"])
    assert sorted(won["alice"] + won["bob"]) == list(range(1, 2 * ROUNDS + 1))

    t = cat.get_table("db.race")
    sm = t.store.snapshot_manager
    assert sm.latest_snapshot_id() == 2 * ROUNDS
    # each (user, identifier) appears exactly once in the chain: a lost CAS
    # round was retried, never re-applied
    seen = set()
    for sid in range(1, 2 * ROUNDS + 1):
        snap = sm.snapshot(sid)
        key = (snap.commit_user, snap.commit_identifier)
        assert key not in seen, f"identifier committed twice: {key}"
        seen.add(key)
    # physical record count == unique keys: double-applied ADDs cannot hide
    assert sm.latest_snapshot().total_record_count == 2 * ROUNDS
    rb = t.new_read_builder()
    rows = dict(rb.new_read().read_all(rb.new_scan().plan()).to_pylist())
    assert rows == {
        **{1000 + i: float(i) for i in range(1, ROUNDS + 1)},
        **{2000 + i: float(i) for i in range(1, ROUNDS + 1)},
    }
