"""Batched point-get serving (ISSUE 13): get_batch parity vs the scalar
lookup() walk and a pandas-style fold, bloom key-index pruning, the
read-your-writes delta tier, refresh() per-bucket diffing, serving
endpoints (KV server + Flight) with typed BUSY, and the (name, level)
compaction-chain cancel regression the RYW soak surfaced."""

import numpy as np
import pytest

from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.metrics import get_metrics
from paimon_tpu.types import BIGINT, DOUBLE, STRING, RowType

SCHEMA = RowType.of(("id", BIGINT()), ("name", STRING()), ("v", DOUBLE()))
STR_SCHEMA = RowType.of(("code", STRING()), ("grp", STRING()), ("v", DOUBLE()))


@pytest.fixture
def cat(tmp_warehouse):
    return FileSystemCatalog(tmp_warehouse, commit_user="pg")


def write(t, data, kinds=None):
    wb = t.new_batch_write_builder()
    w = wb.new_write()
    w.write(data, kinds)
    wb.new_commit().commit(w.prepare_commit())


def scalar_oracle(q, keys, partition=()):
    out = []
    for k in keys:
        row = q.lookup(partition, k)
        out.append(None if row is None else row.to_pylist()[0])
    return out


# ---------------------------------------------------------------------------
# randomized parity: get_batch == scalar lookup() loop == dict fold
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("bloom", [True, False])
@pytest.mark.parametrize("schema_kind", ["int", "dict"])
def test_get_batch_parity_randomized(cat, seed, bloom, schema_kind):
    from paimon_tpu.table.query import LocalTableQuery

    rng = np.random.default_rng(seed)
    opts = {
        "bucket": str(int(rng.integers(1, 4))),
        "file-index.bloom-filter.primary-key.enabled": str(bloom).lower(),
    }
    if schema_kind == "dict":
        opts.update({
            "format.parquet.decoder": "native",
            "format.parquet.encoder": "native",
            "merge.dict-domain": "true",
        })
        schema, key = STR_SCHEMA, "code"
        keyspace = [f"k{i:05d}" for i in range(400)]
    else:
        schema, key = SCHEMA, "id"
        keyspace = list(range(400))
    t = cat.create_table(f"db.p_{schema_kind}_{seed}_{int(bloom)}", schema,
                         primary_keys=[key], options=opts)
    fold = {}
    for commit in range(4):
        n = int(rng.integers(20, 80))
        ks = [keyspace[i] for i in rng.integers(0, len(keyspace), n)]
        ks = list(dict.fromkeys(ks))  # unique per commit
        deleted = rng.random(len(ks)) < 0.15
        vals = [float(commit * 100 + i) for i in range(len(ks))]
        if schema_kind == "dict":
            rows = {"code": ks, "grp": [f"g{hash(k) % 5}" for k in ks], "v": vals}
        else:
            rows = {"id": ks, "name": [f"n{k}" for k in ks], "v": vals}
        kinds = ["-D" if d else "+I" for d in deleted]
        write(t, rows, kinds)
        for k, d, i in zip(ks, deleted, range(len(ks))):
            if d:
                fold.pop(k, None)
            else:
                if schema_kind == "dict":
                    fold[k] = (k, f"g{hash(k) % 5}", vals[i])
                else:
                    fold[k] = (k, f"n{k}", vals[i])
    q = LocalTableQuery(t)
    probe = [keyspace[i] for i in rng.integers(0, len(keyspace), 120)]
    probe += ["zzz-absent", "absent2"] if schema_kind == "dict" else [99999, -5]
    got = q.get_batch(probe).to_pylist()
    assert got == scalar_oracle(q, probe)
    assert got == [fold.get(k) for k in probe]


def test_get_batch_parity_engines(cat):
    """merge.engine=mesh tables serve identical batched gets (the
    write/merge engines change file contents' layout, never the served
    rows)."""
    from paimon_tpu.table.query import LocalTableQuery

    for name, extra in (("mesh", {"merge.engine": "mesh"}),):
        t = cat.create_table(f"db.eng_{name}", SCHEMA, primary_keys=["id"],
                             options={"bucket": "2", **extra})
        write(t, {"id": list(range(60)), "name": [f"n{i}" for i in range(60)],
                  "v": [float(i) for i in range(60)]})
        write(t, {"id": [7], "name": ["seven"], "v": [77.0]})
        write(t, {"id": [9], "name": [None], "v": [None]}, kinds=["-D"])
        q = LocalTableQuery(t)
        probe = [7, 9, 0, 59, 1234]
        got = q.get_batch(probe).to_pylist()
        assert got == scalar_oracle(q, probe)
        assert got[0] == (7, "seven", 77.0) and got[1] is None and got[4] is None


def test_get_batch_dynamic_bucket(cat):
    from paimon_tpu.table.query import LocalTableQuery

    t = cat.create_table("db.dyn", SCHEMA, primary_keys=["id"],
                         options={"bucket": "-1", "dynamic-bucket.target-row-num": "10"})
    write(t, {"id": list(range(40)), "name": ["x"] * 40, "v": [float(i) for i in range(40)]})
    q = LocalTableQuery(t)
    probe = [0, 17, 39, 555]
    assert q.get_batch(probe).to_pylist() == scalar_oracle(q, probe)


def test_get_batch_input_shapes(cat):
    from paimon_tpu.data.batch import ColumnBatch
    from paimon_tpu.table.query import LocalTableQuery

    t = cat.create_table("db.shapes", SCHEMA, primary_keys=["id"], options={"bucket": "1"})
    write(t, {"id": [1, 2], "name": ["a", "b"], "v": [1.0, 2.0]})
    q = LocalTableQuery(t)
    expect = [(1, "a", 1.0), None]
    assert q.get_batch([1, 3]).to_pylist() == expect
    assert q.get_batch([(1,), (3,)]).to_pylist() == expect
    assert q.get_batch({"id": [1, 3]}).to_pylist() == expect
    key_schema = t.row_type.project(["id"])
    assert q.get_batch(ColumnBatch.from_pydict(key_schema, {"id": [1, 3]})).to_pylist() == expect
    res = q.get_batch([2, 9])
    assert res.row(0) == (2, "b", 2.0) and res.row(1) is None
    assert q.get_batch([]).to_pylist() == []


# ---------------------------------------------------------------------------
# bloom key-index pruning
# ---------------------------------------------------------------------------

def test_bloom_key_index_prunes_without_data_io(cat):
    """Two files with interleaved key ranges (min/max cannot tell them
    apart): probing a key only ONE file holds must bloom-prune the other —
    with zero data IO. Out-of-range probes are range-pruned even without
    blooms; with bloom-prune disabled the index is never consulted."""
    from paimon_tpu.format.fileindex import resolve_key_bloom

    if not resolve_key_bloom("true"):
        pytest.skip("PAIMON_TPU_KEY_BLOOM forced off: no key indexes to consult")
    t = cat.create_table("db.bloom", SCHEMA, primary_keys=["id"], options={
        "bucket": "1", "write-only": "true",
        "file-index.bloom-filter.primary-key.enabled": "true",
    })
    write(t, {"id": list(range(0, 400, 2)), "name": ["e"] * 200, "v": [0.0] * 200})
    write(t, {"id": list(range(1, 400, 2)), "name": ["o"] * 200, "v": [1.0] * 200})
    from paimon_tpu.table.query import LocalTableQuery

    q = LocalTableQuery(t)
    g = get_metrics()
    # odd-only probes: the even file's key range covers them, only its
    # bloom can rule them out. 20 single-key probes: P(no prune at
    # fpp=0.001) is negligible
    pruned0 = g.counter("files_pruned").count
    for k in range(1, 41, 2):
        assert q.get_batch([k]).to_pylist() == [(k, "o", 1.0)]
    assert g.counter("files_pruned").count > pruned0
    assert g.counter("index_hits").count > 0
    # out-of-range probes: range pruning alone skips BOTH files
    pruned1 = g.counter("files_pruned").count
    assert q.get_batch([-5, 5000]).to_pylist() == [None, None]
    assert g.counter("files_pruned").count >= pruned1 + 2
    # bloom-prune off: the key index is never consulted
    t2 = t.copy({"lookup.get.bloom-prune.enabled": "false"})
    q2 = LocalTableQuery(t2)
    hits0 = g.counter("index_hits").count
    assert q2.get_batch([398, 399]).to_pylist() == [(398, "e", 0.0), (399, "o", 1.0)]
    assert g.counter("index_hits").count == hits0


def test_key_bloom_payload_roundtrip():
    from paimon_tpu.data.batch import ColumnBatch
    from paimon_tpu.format.fileindex import FileIndexPredicate, build_index_payload
    from paimon_tpu.table.bucket import key_hashes

    schema = RowType.of(("a", BIGINT()), ("b", STRING()))
    batch = ColumnBatch.from_pydict(schema, {"a": [1, 2, 3], "b": ["x", "y", "z"]})
    hashes = key_hashes(batch, ["a", "b"])
    payload = build_index_payload(batch, [], key_hashes=hashes)
    pred = FileIndexPredicate.from_bytes(payload)
    assert pred.key_bloom() is not None
    mask = pred.test_key_hashes(hashes)
    assert mask.all()  # every written key might be present
    other = ColumnBatch.from_pydict(schema, {"a": [100 + i for i in range(64)], "b": ["q"] * 64})
    miss = pred.test_key_hashes(key_hashes(other, ["a", "b"]))
    assert not miss.all()  # fpp 0.001: essentially all absents excluded


def test_key_hashes_code_domain_parity():
    """The pool-gather fast path must hash bit-identically to expanded
    values — routing and bloom probes depend on it."""
    from paimon_tpu.data.batch import Column, ColumnBatch
    from paimon_tpu.table.bucket import key_hashes

    schema = RowType.of(("s", STRING()),)
    pool = np.array(["aa", "bb", "cc"], dtype=object)
    codes = np.array([2, 0, 1, 1, 2], dtype=np.uint32)
    coded = Column.from_codes(pool, codes)
    expanded = Column(pool.take(codes))
    b1 = ColumnBatch(schema, {"s": coded})
    b2 = ColumnBatch(schema, {"s": expanded})
    assert np.array_equal(key_hashes(b1, ["s"]), key_hashes(b2, ["s"]))


# ---------------------------------------------------------------------------
# read-your-writes
# ---------------------------------------------------------------------------

def test_read_your_writes_tiers(cat):
    from paimon_tpu.table.query import LocalTableQuery
    from paimon_tpu.table.write import TableWrite

    t = cat.create_table("db.ryw", SCHEMA, primary_keys=["id"], options={"bucket": "2"})
    write(t, {"id": [1, 2], "name": ["a", "b"], "v": [1.0, 2.0]})
    q = LocalTableQuery(t)
    tw = TableWrite(t)
    q.attach_write(tw)
    tw.write({"id": [2, 5], "name": ["b2", "e"], "v": [20.0, 50.0]})
    g = get_metrics()
    m0 = g.counter("memtable_hits").count
    assert q.get_batch([1, 2, 5, 9]).to_pylist() == [
        (1, "a", 1.0), (2, "b2", 20.0), (5, "e", 50.0), None]
    assert g.counter("memtable_hits").count > m0
    # buffered delete masks a committed row
    tw.write({"id": [1], "name": [None], "v": [None]}, kinds=["-D"])
    assert q.get_batch([1]).to_pylist() == [None]
    # flushed-but-uncommitted level-0 files stay visible
    for w in tw._writers.values():
        w.flush()
    assert q.get_batch([1, 2, 5]).to_pylist() == [None, (2, "b2", 20.0), (5, "e", 50.0)]
    # after commit + refresh the same state serves from the snapshot
    t.new_batch_write_builder().new_commit().commit(tw.prepare_commit())
    tw.close()
    q.attach_write(None)
    q.refresh()
    assert q.get_batch([1, 2, 5]).to_pylist() == [None, (2, "b2", 20.0), (5, "e", 50.0)]


# ---------------------------------------------------------------------------
# refresh() per-bucket diff
# ---------------------------------------------------------------------------

def test_refresh_diff_keeps_unchanged_buckets(cat):
    from paimon_tpu.table.query import LocalTableQuery

    t = cat.create_table("db.diff", SCHEMA, primary_keys=["id"], options={"bucket": "4"})
    write(t, {"id": list(range(40)), "name": ["x"] * 40, "v": [float(i) for i in range(40)]})
    q = LocalTableQuery(t)
    before_levels = dict(q._levels)
    before_idx = dict(q._get_indexes)
    write(t, {"id": [0], "name": ["y"], "v": [100.0]})  # lands in ONE bucket
    q.refresh()
    changed = [pb for pb in before_levels if q._levels[pb] is not before_levels[pb]]
    unchanged = [pb for pb in before_levels if q._levels[pb] is before_levels[pb]]
    assert len(changed) == 1 and len(unchanged) == 3
    assert all(q._get_indexes[pb] is before_idx[pb] for pb in unchanged)
    assert q.get_batch([0]).to_pylist() == [(0, "y", 100.0)]
    # same snapshot: refresh is a no-op
    ids = {pb: id(v) for pb, v in q._levels.items()}
    q.refresh()
    assert {pb: id(v) for pb, v in q._levels.items()} == ids


# ---------------------------------------------------------------------------
# serving endpoints
# ---------------------------------------------------------------------------

def test_kv_server_get_batch_and_typed_busy(cat):
    from paimon_tpu.service import KvBusyError, KvQueryClient, KvQueryServer

    t = cat.create_table("db.srv", SCHEMA, primary_keys=["id"], options={"bucket": "2"})
    write(t, {"id": [1, 2, 3], "name": ["a", "b", "c"], "v": [1.0, 2.0, 3.0]})
    srv = KvQueryServer(t, max_inflight_gets=1)
    host, port = srv.start()
    try:
        c = KvQueryClient(host, port)
        assert c.get_batch([1, 2, 99]) == [(1, "a", 1.0), (2, "b", 2.0), None]
        # saturate the admission gate: the next get must shed TYPED
        assert srv._get_gate.acquire(blocking=False)
        try:
            with pytest.raises(KvBusyError) as ei:
                c.get_batch([1])
            assert ei.value.payload["state"] == "busy-reads"
            assert ei.value.retry_after_ms > 0
        finally:
            srv._get_gate.release()
        assert c.get_batch([1]) == [(1, "a", 1.0)]
        c.close()
    finally:
        srv.shutdown()


def test_kv_server_read_your_writes(cat):
    from paimon_tpu.service import KvQueryClient, KvQueryServer
    from paimon_tpu.table.write import TableWrite

    t = cat.create_table("db.srv2", SCHEMA, primary_keys=["id"], options={"bucket": "1"})
    write(t, {"id": [1], "name": ["a"], "v": [1.0]})
    tw = TableWrite(t)
    srv = KvQueryServer(t, table_write=tw)
    host, port = srv.start()
    try:
        tw.write({"id": [9], "name": ["buf"], "v": [9.0]})
        c = KvQueryClient(host, port)
        assert c.get_batch([1, 9]) == [(1, "a", 1.0), (9, "buf", 9.0)]
        c.close()
    finally:
        srv.shutdown()
        tw.close()


def test_flight_get_batch(tmp_warehouse):
    pytest.importorskip("pyarrow.flight")
    from paimon_tpu.service.flight import PaimonFlightServer, flight_get_batch

    cat = FileSystemCatalog(tmp_warehouse, commit_user="fl")
    t = cat.create_table("db.fg", SCHEMA, primary_keys=["id"], options={"bucket": "2"})
    write(t, {"id": [1, 2, 3], "name": ["a", "b", "c"], "v": [1.0, 2.0, 3.0]})
    srv = PaimonFlightServer(tmp_warehouse)
    loc = srv.start()
    try:
        assert flight_get_batch(loc, "db.fg", [2, 44]) == [(2, "b", 2.0), None]
        # refresh-on-action: new commits are visible to subsequent actions
        write(t, {"id": [44], "name": ["d"], "v": [44.0]})
        assert flight_get_batch(loc, "db.fg", [44]) == [(44, "d", 44.0)]
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# regression: compaction-chain cancel must key on (name, level)
# ---------------------------------------------------------------------------

def test_compaction_chain_upgrade_keeps_rows(cat):
    """One commit chaining rewrite([L0 runs]) -> F@mid then upgrade F@mid ->
    F@max lost F entirely under the old name-keyed cancel: the upgrade's
    DELETE(F@mid)/ADD(F@max) share F's name with round 1's ADD(F@mid), so
    the whole chain cancelled — the message deleted the L0 inputs but never
    added F (rows silently dropped, the file left to the orphan sweep). The
    (name, LEVEL) key cancels only the true create-then-consume pair.

    Setup: runs at L5 (big) and L4 (mid, so the size-ratio pick's first
    EXCLUDED run is non-zero-level and round 1 outputs BELOW max), all key
    ranges disjoint so the full pass sees singleton sections and upgrades."""
    from paimon_tpu.core.kv import KVBatch
    from paimon_tpu.core.manifest import CommitMessage, ManifestCommittable
    from paimon_tpu.data.batch import ColumnBatch

    t = cat.create_table("db.chain", SCHEMA, primary_keys=["id"], options={
        "bucket": "1", "write-buffer-rows": "8",
    })
    store = t.store
    wf = store.writer_factory((), 0)

    def mk(ids, seq0, level):
        batch = ColumnBatch.from_pydict(
            SCHEMA, {"id": ids, "name": [f"n{k}" for k in ids], "v": [float(k) for k in ids]}
        )
        return wf.write(KVBatch.from_rows(batch, seq0), level=level)

    metas = mk(list(range(0, 10000)), 0, 5) + mk(list(range(20000, 23000)), 10000, 4)
    store.new_commit().commit(ManifestCommittable(1, messages=[
        CommitMessage(partition=(), bucket=0, total_buckets=1, new_files=metas)
    ]))
    # ONE commit: 6 small flushes (auto-compaction rewrites the L0 runs to a
    # mid level), then a full compaction that UPGRADES that output to max
    wb = t.new_batch_write_builder()
    w = wb.new_write()
    for i in range(6):
        ids = [50000 + i * 10 + j for j in range(8)]
        w.write({"id": ids, "name": [f"n{k}" for k in ids], "v": [float(k) for k in ids]})
    w.compact(full=True)
    wb.new_commit().commit(w.prepare_commit())
    rb = t.new_read_builder()
    batch = rb.new_read().read_all(rb.new_scan().plan())
    got = set(batch.column("id").values.tolist())
    expect = (
        set(range(10000)) | set(range(20000, 23000))
        | {50000 + i * 10 + j for i in range(6) for j in range(8)}
    )
    missing = sorted(expect - got)
    assert not missing, f"rows lost by the compaction-chain cancel: {missing[:10]}"
    assert batch.num_rows == len(expect)  # and nothing double-counted


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_get_metric_group(cat):
    from paimon_tpu.table.query import LocalTableQuery

    t = cat.create_table("db.met", SCHEMA, primary_keys=["id"], options={
        "bucket": "1", "file-index.bloom-filter.primary-key.enabled": "true"})
    write(t, {"id": [1, 2], "name": ["a", "b"], "v": [1.0, 2.0]})
    q = LocalTableQuery(t)
    g = get_metrics()
    gets0 = g.counter("gets").count
    probed0 = g.counter("keys_probed").count
    q.get_batch([1, 2, 3])
    assert g.counter("gets").count == gets0 + 3
    assert g.counter("keys_probed").count > probed0
    assert g.histogram("probe_ms").count > 0
