"""merge.engine = mesh: which jobs share a shard_map call is the plan's to
decide (ISSUE 29). A reader's batches are rounds of its plan, the next `axis`
data splits in split order, so the calls an operation makes, the rows it pads
and what it counts under merge{...} are the same in every operation, whatever
the feeder threads' timing; and the output is the last writer of every key.

The table is the benchmark cell's at a test's size: 8 buckets, 4 overlapping
sorted runs, write-only, read on a bucket axis of 4 (the cell's four chips) and
of 8 (the suite's virtual devices)."""

import time

import numpy as np
import pytest

import jax

import paimon_tpu as pt
from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.metrics import registry
from paimon_tpu.ops.merge import pad_size

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs four or more devices (the virtual CPU mesh or a four-chip host)"
)

BUCKETS, RUNS, KEYS = 8, 4, 4000
SCHEMA = pt.RowType.of(("id", pt.BIGINT(False)), ("v", pt.BIGINT()), ("d", pt.DOUBLE()), ("s", pt.STRING()))
MERGE_COUNTERS = ("rows_in", "pad_rows", "h2d_bytes", "d2h_bytes", "merges", "tiles", "winners")


def _runs(seed):
    """RUNS overlapping sorted runs over KEYS keys: every key has a home run
    and a fifth of them are written once more by a later one. Returns the
    runs and, per key, the run that wrote it last."""
    rng = np.random.default_rng(seed)
    ids = np.arange(KEYS, dtype=np.int64) * 3 + 1
    home = rng.integers(0, RUNS, KEYS)
    again = np.where(rng.random(KEYS) < 0.2, rng.integers(0, RUNS, KEYS), home)
    last = np.maximum(home, again)
    return [ids[(home == r) | (again == r)] for r in range(RUNS)], ids, last


def _columns(ids, run):
    """Every column a function of (key, writing run); `run` one number or one a key."""
    run = np.broadcast_to(run, ids.shape)
    return {
        "id": ids,
        "v": ids * 10 + run,
        "d": ids * 0.5 + run,
        "s": np.array([f"r{r}-{i % 7}" for i, r in zip(ids.tolist(), run.tolist())], dtype=object),
    }


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    """The same rows twice, merge.engine=mesh and single, and the plain
    last-writer reference as columns sorted by key. Written once: the tests
    only read, and count by differences of the registry."""
    cat = FileSystemCatalog(str(tmp_path_factory.mktemp("mesh-rounds")), commit_user="mesh-rounds")
    opts = {"bucket": str(BUCKETS), "write-only": "true"}
    mesh_t = cat.create_table("db.mesh", SCHEMA, primary_keys=["id"], options={**opts, "merge.engine": "mesh"})
    single_t = cat.create_table("db.single", SCHEMA, primary_keys=["id"], options=opts)
    runs, ids, last = _runs(29)
    for t in (mesh_t, single_t):
        for r, run_ids in enumerate(runs):
            wb = t.new_batch_write_builder()
            w = wb.new_write()
            w.write(_columns(run_ids, r))
            wb.new_commit().commit(w.prepare_commit())
    return mesh_t, single_t, _columns(ids, last), sum(len(r) for r in runs)


def _read(t):
    rb = t.new_read_builder()
    return rb.new_read().read_all(rb.new_scan().plan())


def _by_key(batch):
    order = np.argsort(np.asarray(batch.column("id").values), kind="stable")
    return {n: np.asarray(batch.column(n).values)[order].tolist() for n in batch.schema.field_names}


@pytest.fixture(params=[4, 8])
def axis(request, monkeypatch):
    """A bucket axis of `param` devices for every MeshExecutor built inside
    the test: 4 is the benchmark cell's host."""
    from paimon_tpu.parallel import mesh_exec
    from paimon_tpu.parallel.mesh import make_mesh

    n = request.param
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")
    meshes = (make_mesh(n), make_mesh(n, bucket_parallel=1))
    monkeypatch.setattr(mesh_exec, "_meshes", lambda: meshes)
    return n


def _deltas(before, after):
    def of(group, names):
        return {n: after.get(group, {}).get(n, 0) - before.get(group, {}).get(n, 0) for n in names}

    return {"mesh": of("mesh", ("shards", "pad_rows", "buckets_sharded")), "merge": of("merge", MERGE_COUNTERS)}


def test_mesh_read_is_the_last_writer_and_the_single_engine_read(tables, axis):
    mesh_t, single_t, want, _ = tables
    got = _by_key(_read(mesh_t))
    assert got == {n: np.asarray(v).tolist() for n, v in want.items()}
    # bit-identical to the single-device path, row order included
    assert _read(mesh_t).to_pylist() == _read(single_t).to_pylist()


@pytest.mark.parametrize("slow_split", [None, 0, 5], ids=["free", "first-split-slow", "sixth-split-slow"])
def test_an_operations_calls_and_counters_are_the_plans(tables, axis, slow_split, monkeypatch):
    """Ten operations, the same deltas every time; with one split's decode
    held back so that the feeder has submitted later rounds' jobs (or not yet
    this round's) when the consumer resolves: the race, made to happen."""
    mesh_t, _, want, rows_in = tables
    if slow_split is not None:
        from paimon_tpu.table.read import TableRead

        dispatch = TableRead._dispatch

        def slowed(self, split):
            if split.bucket == slow_split:
                time.sleep(0.15)
            return dispatch(self, split)

        monkeypatch.setattr(TableRead, "_dispatch", slowed)
    splits = mesh_t.new_read_builder().new_scan().plan()
    assert [s.bucket for s in splits] == list(range(BUCKETS))
    rounds = [splits[i : i + axis] for i in range(0, BUCKETS, axis)]
    # a round is one call of `axis` shards, each padded to the round's largest
    alloc = sum(axis * max(pad_size(s.row_count) for s in r) for r in rounds)
    seen = []
    for _ in range(10):
        before = registry.snapshot()
        out = _read(mesh_t)
        seen.append(_deltas(before, registry.snapshot()))
        assert out.num_rows == KEYS
    assert all(d == seen[0] for d in seen), seen
    mesh, merge = seen[0]["mesh"], seen[0]["merge"]
    assert mesh["shards"] == len(rounds) == -(-BUCKETS // axis)
    assert mesh["buckets_sharded"] == BUCKETS
    assert mesh["pad_rows"] == merge["pad_rows"] == alloc - rows_in
    assert merge["rows_in"] == rows_in and merge["winners"] == KEYS
    assert merge["merges"] == merge["tiles"] == BUCKETS
    # one u32 key lane or two, no sequence lane (run order is sequence order), a u32 pad flag
    assert merge["h2d_bytes"] in (alloc * 8, alloc * 12)
    # the packed selection of every shard, whole (int32), and a count a shard (int64)
    assert merge["d2h_bytes"] == alloc * 4 + len(rounds) * axis * 8
    assert _by_key(out)["v"] == np.asarray(want["v"]).tolist()


def test_jobs_outside_a_round_run_together_and_rounds_apart(axis):
    """The executor's own contract: a job resolves with its round's jobs and
    no other's; jobs submitted outside a round (compaction, the writers) run
    with everything else that was submitted so."""
    from paimon_tpu.parallel.mesh_exec import MeshExecutor

    rng = np.random.default_rng(7)
    mex = MeshExecutor()

    def lanes(n):
        return rng.integers(0, 50, (n, 1)).astype(np.uint32)

    def winners(x):  # last row of every key, in key order
        last = {int(k): i for i, k in enumerate(x[:, 0])}
        return [last[k] for k in sorted(last)]

    inputs = [lanes(200 + 10 * i) for i in range(6)]
    # submitted out of order, as feeder threads do: round 1 before round 0 is complete
    ids = {}
    for i in (4, 0, 1, 5):
        ids[i] = mex.round(i // 4).submit_dedup(inputs[i], None)
    loose = [mex.submit_dedup(inputs[i], None) for i in (2, 3)]
    assert mex.result(ids[0]).tolist() == winners(inputs[0])
    assert mex.executed_batches == 1 and set(mex._jobs) == {ids[4], ids[5], *loose}
    assert mex.result(loose[1]).tolist() == winners(inputs[3])
    assert mex.executed_batches == 2 and set(mex._jobs) == {ids[4], ids[5]}
    assert mex.result(ids[5]).tolist() == winners(inputs[5])
    assert mex.executed_batches == 3 and not mex._jobs
    for i, jid in ((1, ids[1]), (4, ids[4]), (2, loose[0])):
        assert mex.result(jid).tolist() == winners(inputs[i])
    assert mex.executed_batches == 3


# ---- spans and program names on the mesh path (docs/tracing.md) ------------

MESH_SPANS = {  # span -> the span that causes it
    "mesh.feed": "read_all", "split": "read_all", "mesh.plan": "split", "mesh.stack": "split", "mesh.batch": "split",
    "mesh.h2d": "mesh.batch", "mesh.run": "mesh.batch", "mesh.d2h": "mesh.batch", "gather": "split",
    "gather.plan": "gather"}


@pytest.fixture
def traced_mesh_read(tables, axis, tmp_path):
    """One mesh read under a profiler session: its `pt:` events as (name,
    start_ns, end_ns, line, stats), and what it counted under read{...}."""
    import glob

    mesh_t, _, _, rows_in = tables
    from paimon_tpu.utils.cache import clear_all

    _read(mesh_t)  # compile outside the session
    clear_all()  # and decode inside it: a data-file cache hit opens no decode.file
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    options.python_tracer_level = 0
    before = registry.snapshot()["read"]
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _read(mesh_t)
    finally:
        jax.profiler.stop_trace()
    counted = {k: v - before.get(k, 0) for k, v in registry.snapshot()["read"].items()}
    path = sorted(glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")))[-1]
    return [(e.name[3:], e.start_ns, e.start_ns + e.duration_ns, (pi, li), dict(e.stats))
            for pi, plane in enumerate(jax.profiler.ProfileData.from_file(path).planes)
            for li, line in enumerate(plane.lines) for e in line.events if e.name.startswith("pt:")], rows_in, counted


def _by_name(events):
    by_name = {}
    for e in events:
        by_name.setdefault(e[0], []).append(e)
    return by_name


def test_a_traced_mesh_read_opens_the_spans_nested_and_numbered(traced_mesh_read, axis):
    events, rows_in, _ = traced_mesh_read
    (read_all,) = [e for e in events if e[0] == "read_all"]
    op, reader = read_all[4]["op"], read_all[3]
    calls = -(-BUCKETS // axis)
    by_name = _by_name(events)
    for name, parent in MESH_SPANS.items():
        assert by_name.get(name), (name, sorted(by_name))
        mine = [e for e in by_name[name] if e[3] == reader]
        assert mine and all(e[4]["op"] == op and e[4]["parent"] == parent for e in mine), name
        assert all(any(p[3] == reader and p[1] <= e[1] and e[2] <= p[2] for p in by_name[parent]) for e in mine), name
    # one of each a call, on the reading thread, with the call's numbers
    for name in ("mesh.plan", "mesh.stack", "mesh.batch", "mesh.h2d", "mesh.run", "mesh.d2h"):
        assert len(by_name[name]) == calls and all(e[3] == reader for e in by_name[name]), name
    assert len(by_name["mesh.feed"]) == BUCKETS
    assert sum(e[4]["rows"] for e in by_name["mesh.plan"]) == rows_in
    assert sum(e[4]["jobs"] for e in by_name["mesh.plan"]) == BUCKETS
    assert all(e[4]["shards"] == axis for e in by_name["mesh.batch"])
    stacked = sum(e[4]["rows"] for e in by_name["mesh.stack"])
    assert sum(e[4]["pad_rows"] for e in by_name["mesh.batch"]) == stacked - rows_in
    assert sum(e[4]["pad_rows"] for e in by_name["mesh.h2d"]) == stacked - rows_in
    assert sum(e[4]["tiles"] for e in by_name["mesh.h2d"]) == BUCKETS
    assert sum(e[4]["h2d_bytes"] for e in by_name["mesh.h2d"]) in (stacked * 8, stacked * 12)
    assert sum(e[4]["winners"] for e in by_name["mesh.d2h"]) == KEYS
    assert sum(e[4]["d2h_bytes"] for e in by_name["mesh.d2h"]) == stacked * 4 + calls * axis * 8
    # what the feeder's threads open for a split names the operation too
    for name in ("pipeline.scan", "decode.keys", "decode.values", "decode.file", "lanes.encode"):
        others = [e for e in by_name.get(name, []) if e[3] != reader]
        assert others and all(e[4]["op"] == op for e in others), name
    assert len([e for e in by_name["split"] if e[3] != reader]) == BUCKETS  # the dispatch half, on the feeder


def test_a_mesh_read_takes_the_keys_only_pipeline(traced_mesh_read, axis):
    """ISSUE 30: under a round a split sends its key lanes only and takes its
    winners from the per-file value parts: the two decode passes on the feeder's
    threads, the gather's plan on the reading thread, a column a task on the pool."""
    events, rows_in, counted = traced_mesh_read
    columns = len(SCHEMA.fields)
    # every column, seq and kind of every winner (all +I here); from the parts the numeric value columns v and d:
    # pyarrow joins the chunks of the arrow-backed s inside its take, and id, seq, kind come from the key pass
    assert counted["rows_gathered"] == KEYS * (columns + 2)
    assert counted["rows_gathered_from_parts"] == KEYS * 2
    by_name = _by_name(events)
    (read_all,) = by_name["read_all"]
    op, reader = read_all[4]["op"], read_all[3]
    assert "decode.all" not in by_name
    for name in ("decode.keys", "decode.values"):
        assert len(by_name[name]) == BUCKETS and all(e[3] != reader and e[4]["files"] == RUNS for e in by_name[name]), name
    assert sorted(e[4]["pass"] for e in by_name["decode.file"]) == ["keys"] * BUCKETS * RUNS + ["values"] * BUCKETS * RUNS
    # a feeder thread joins the key pass alone; whole rows are joined by the reader (sections, then splits)
    feeder_concats = [e for e in by_name["concat"] if e[3] != reader]
    assert len(feeder_concats) == BUCKETS and all(e[4]["columns"] == 1 for e in feeder_concats)
    assert sum(e[4]["rows"] for e in feeder_concats) == rows_in
    for name in ("gather", "gather.plan"):
        assert len(by_name[name]) == BUCKETS and all(e[3] == reader for e in by_name[name]), name
    assert all(e[4]["parts"] == RUNS and e[4]["columns"] == columns for e in by_name["gather"])
    assert sum(e[4]["rows_in"] for e in by_name["gather"]) == rows_in
    assert sum(e[4]["rows_out"] for e in by_name["gather"]) == KEYS
    cols = by_name["gather.column"]
    assert sorted(e[4]["column"] for e in cols) == sorted(["id", "v", "d", "s", "_seq", "_kind"] * BUCKETS)
    # kind first and on the reading thread: it says whether the winners may be written into read_all's result
    assert all((e[3] == reader) == (e[4]["column"] == "_kind") and e[4]["op"] == op and e[4]["parent"] == "gather" for e in cols)
    assert all(e[4]["parts"] == (RUNS if e[4]["column"] in ("v", "d", "s") else 1) for e in cols)


# ---- what stays on the whole-batch merge under the mesh engine ---------------

def _kinds(ids, run):
    """A tenth of a later run's rows are deletes."""
    return ["-D" if run and i % 10 == run else "+I" for i in ids.tolist()]


WHOLE_BATCH = {  # case -> (table options, a write's kinds)
    "deletion-vector": ({"deletion-vectors.enabled": "true"}, None),
    "partial-update": ({"merge-engine": "partial-update"}, None),
    "ignore-delete": ({"ignore-delete": "true"}, _kinds),
    "sequence-field": ({"sequence.field": "v"}, None),
}


@pytest.mark.parametrize("case", list(WHOLE_BATCH))
def test_the_paths_that_stay_on_the_whole_batch_merge_read_as_the_single_engine(case, axis, tmp_path):
    """A split with a deletion vector, another merge engine, ignore-delete and
    a user sequence field need more than the key pass to pick a winner: they
    keep `_read_files` + `merge_async` under a round, and read what the single
    engine reads."""
    from paimon_tpu.data.predicate import less_than

    options, kinds = WHOLE_BATCH[case]
    cat = FileSystemCatalog(str(tmp_path), commit_user="mesh-rounds")
    opts = {"bucket": str(BUCKETS), "write-only": "true", **options}
    mesh_t = cat.create_table("db.mesh", SCHEMA, primary_keys=["id"], options={**opts, "merge.engine": "mesh"})
    single_t = cat.create_table("db.single", SCHEMA, primary_keys=["id"], options=opts)
    runs, ids, _ = _runs(30)
    for t in (mesh_t, single_t):
        for r, run_ids in enumerate(runs):
            wb = t.new_batch_write_builder()
            w = wb.new_write()
            cols = _columns(run_ids, r)
            if case == "sequence-field":  # the first writer wins: the user's order is not the run order
                cols["v"] = run_ids * 10 - r
            w.write(cols, **({"kinds": kinds(run_ids, r)} if kinds else {}))
            wb.new_commit().commit(w.prepare_commit())
        if case == "deletion-vector":  # some 130 keys over 8 buckets: every split has a vector
            assert t.delete_where(less_than("id", 400)) > BUCKETS
    splits = mesh_t.new_read_builder().new_scan().plan()
    assert len(splits) == BUCKETS and (case != "deletion-vector" or all(s.dv_index_file for s in splits))
    before = registry.snapshot()
    got = _read(mesh_t)
    now = registry.snapshot()
    assert now["mesh"]["buckets_sharded"] - before["mesh"]["buckets_sharded"] == BUCKETS  # the rounds ran them
    assert now["read"].get("rows_gathered_from_parts", 0) == before["read"].get("rows_gathered_from_parts", 0)
    want = _read(single_t)
    assert 0 < got.num_rows <= KEYS and got.to_pylist() == want.to_pylist()
    if case == "sequence-field":
        first = {}
        for r, run_ids in enumerate(runs):
            for i in run_ids.tolist():
                first.setdefault(i, r)
        assert _by_key(got)["v"] == [i * 10 - first[i] for i in ids.tolist()]


@pytest.mark.parametrize("name,build", [
    ("dedup_select_mesh", "bucket_parallel_dedup_fn"), ("merge_plan_mesh", "bucket_parallel_plan_fn")])
def test_the_shard_map_programs_have_names_the_sort_readers_take(axis, name, build):
    """`jit_dedup_select_mesh` / `jit_merge_plan_mesh` on the trace's XLA
    Modules line: perfbench's sort and non-sort readers go by these prefixes."""
    from paimon_tpu.parallel import merge as PM
    from paimon_tpu.parallel.mesh_exec import _meshes

    fn = getattr(PM, build)(_meshes()[0], 1, 0)
    shapes = [jax.ShapeDtypeStruct(s, np.uint32) for s in ((axis, 128, 1), (axis, 128, 0), (axis, 128))]
    assert f"module @jit_{name} " in fn.lower(*shapes).as_text()
    assert f"jit_{name}".startswith(("jit_dedup_select", "jit_merge_plan"))
