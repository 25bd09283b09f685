"""Mesh-sharded execution layer (merge.engine = mesh): randomized-oracle
parity against the single-device path, global lane planning, key-axis
range-shuffle, feeder behavior, and the cpu fallback (ISSUE 7).

Everything here runs on the 8-device virtual CPU mesh the conftest forces
(or, under PAIMON_TEST_PLATFORM=tpu, on the chips of a multi-chip host);
the contract under test is BIT-IDENTICAL output: a mesh table and a
single-engine table fed the same rows must read back equal, row for row, in
order — across merge engines, bucket counts that don't divide the mesh
evenly, empty buckets, and padded shards."""

import os

import numpy as np
import pytest

import jax

import paimon_tpu as pt
from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.metrics import mesh_metrics, registry

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs two or more devices (the virtual CPU mesh or a multi-chip host)"
)

# scripts/verify.sh mesh runs this suite twice, forcing merge.engine both
# ways; with "single" forced the parity assertions still hold (both tables
# collapse to the same path) but engagement counters must not be asserted
MESH_FORCED_OFF = os.environ.get("PAIMON_TPU_MERGE_ENGINE", "").strip().lower() == "single"

SCHEMA = pt.RowType.of(("id", pt.BIGINT(False)), ("a", pt.DOUBLE()), ("s", pt.STRING()))


def _pair(warehouse, name, opts, pk=("id",)):
    """The same logical table twice: merge.engine=mesh and single."""
    cat = FileSystemCatalog(warehouse, commit_user="mesh-exec")
    m = cat.create_table(
        f"db.{name}_mesh", SCHEMA, primary_keys=list(pk), options={**opts, "merge.engine": "mesh"}
    )
    s = cat.create_table(f"db.{name}_single", SCHEMA, primary_keys=list(pk), options=opts)
    return m, s


def _write(t, data):
    wb = t.new_batch_write_builder()
    w = wb.new_write()
    w.write(dict(data))
    wb.new_commit().commit(w.prepare_commit())


def _read(t):
    rb = t.new_read_builder()
    return rb.new_read().read_all(rb.new_scan().plan()).to_pylist()


def _rounds(rng, rounds=3, n=1200, key_space=700, null_rate=0.0):
    out = []
    for r in range(rounds):
        ids = rng.integers(0, key_space, n).astype(np.int64)
        a = ids * 1.0 + r * 1000
        if null_rate:
            a = np.where(rng.random(n) < null_rate, np.nan, a)
        out.append(
            {
                "id": ids,
                "a": a,
                "s": np.array([f"r{r}-{int(i) % 53}" for i in ids], dtype=object),
            }
        )
    return out


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize(
    "scenario,opts",
    [
        ("dedup", {"bucket": "3"}),
        ("dedup8", {"bucket": "8", "write-only": "true"}),
        (
            "pu",
            {"bucket": "3", "merge-engine": "partial-update", "num-sorted-run.compaction-trigger": "2"},
        ),
        (
            "agg",
            {
                "bucket": "5",
                "merge-engine": "aggregation",
                "fields.a.aggregate-function": "sum",
                "num-sorted-run.compaction-trigger": "2",
            },
        ),
    ],
)
def test_mesh_parity_randomized(tmp_warehouse, scenario, opts, seed):
    """mesh == single bit-for-bit across seeds x merge engines x bucket
    counts (3 and 5 don't divide the 8-way mesh: the batch pads to the axis
    and the pad shards must stay inert)."""
    rng = np.random.default_rng(seed)
    mesh_t, single_t = _pair(tmp_warehouse, f"{scenario}{seed}", opts)
    null_rate = 0.3 if scenario == "pu" else 0.0
    registry.reset()
    for data in _rounds(rng, null_rate=null_rate):
        _write(mesh_t, data)
        _write(single_t, data)
    got = _read(mesh_t)
    # engagement may come from the read (overlapping runs) or from the
    # write/compaction merges (engines whose compaction leaves single runs)
    if not MESH_FORCED_OFF:
        assert mesh_metrics().counter("buckets_sharded").count > 0, "mesh engine never engaged"
    assert got == _read(single_t)


def test_mesh_parity_empty_and_skewed_buckets(tmp_warehouse, rng):
    """Keys concentrated on a few hash buckets: some buckets are empty, the
    non-empty set doesn't divide the mesh, and one bucket dominates — the
    padded/stacked shards must not leak rows across jobs."""
    mesh_t, single_t = _pair(tmp_warehouse, "skew", {"bucket": "7"})
    for r in range(2):
        ids = np.concatenate(
            [np.full(900, 11, dtype=np.int64), rng.integers(0, 5, 100).astype(np.int64)]
        )
        data = {
            "id": ids,
            "a": ids * 1.0 + r,
            "s": np.array([f"x{r}-{i % 7}" for i in range(len(ids))], dtype=object),
        }
        _write(mesh_t, data)
        _write(single_t, data)
    got = _read(mesh_t)
    assert got == _read(single_t)
    assert len({row[0] for row in got}) == len(got)  # unique PKs survived the merge


def test_mesh_compaction_and_changelog_parity(tmp_warehouse, rng):
    """Full compaction with the full-compaction changelog producer through
    the mesh: rewrite merges batch over the bucket axis, the changelog diff
    must match the single path exactly (including the produced changelog)."""
    opts = {
        "bucket": "3",
        "changelog-producer": "full-compaction",
        "num-sorted-run.compaction-trigger": "2",
    }
    mesh_t, single_t = _pair(tmp_warehouse, "cl", opts)
    for data in _rounds(rng, rounds=3, n=800, key_space=400):
        _write(mesh_t, data)
        _write(single_t, data)
    for t in (mesh_t, single_t):
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        w.compact(full=True)
        wb.new_commit().commit(w.prepare_commit())
    assert _read(mesh_t) == _read(single_t)
    # the changelog files themselves must agree too
    def changelog(t):
        t2 = t.copy({"incremental-between": "0,99", "incremental-between-scan-mode": "changelog"})
        rb = t2.new_read_builder()
        read = rb.new_read()
        out = []
        for s in rb.new_scan().plan():
            rows, kinds = read.read_with_kinds(s)
            out.append((rows.to_pylist(), kinds.tolist()))
        return out

    assert changelog(mesh_t) == changelog(single_t)


def test_mesh_sort_compact_key_axis_parity(tmp_warehouse, rng):
    """Sort-compact clustering through range_partition_rows over the key
    axis: the distributed stable sort's permutation must equal the
    single-device one (same output rows in the same order), and rows must
    actually move through the exchange."""
    schema = pt.RowType.of(("x", pt.BIGINT(False)), ("y", pt.BIGINT()), ("s", pt.STRING()))
    cat = FileSystemCatalog(tmp_warehouse, commit_user="sc")
    common = {"bucket": "2", "parallel.key-axis.rows": "64"}
    am = cat.create_table("db.sc_mesh", schema, options={**common, "merge.engine": "mesh"})
    asg = cat.create_table("db.sc_single", schema, options=common)
    for r in range(2):
        x = rng.integers(0, 100_000, 2500).astype(np.int64)
        data = {
            "x": x,
            "y": (x * 13) % 997,
            "s": np.array([f"s{int(v) % 37}" for v in x], dtype=object),
        }
        _write(am, data)
        _write(asg, data)
    from paimon_tpu.table.sort_compact import sort_compact

    registry.reset()
    n1 = sort_compact(am, ["y", "x"], order="zorder")
    if not MESH_FORCED_OFF:
        assert mesh_metrics().counter("exchange_rows").count > 0, "key-axis shuffle never ran"
    n2 = sort_compact(asg, ["y", "x"], order="zorder")
    assert n1 == n2
    assert _read(am) == _read(asg)


@pytest.mark.parametrize("via", ["table-read", "merge-async"])
def test_mesh_key_axis_oversized_bucket(tmp_warehouse, rng, via):
    """One bucket past parallel.key-axis.rows leaves the bucket axis and
    range-shuffles its dedup over the key axis — result still bit-identical.
    `table-read`: the threshold is the table's option and the job a read's;
    `merge-async`: the threshold is an installed executor's and the job one
    merge_async dispatch."""
    if via == "table-read":
        opts = {"bucket": "1", "write-only": "true", "parallel.key-axis.rows": "512"}
        mesh_t, single_t = _pair(tmp_warehouse, "huge", opts)
        for data in _rounds(rng, rounds=2, n=3000, key_space=1500):
            _write(mesh_t, data)
            _write(single_t, data)
        registry.reset()
        got = _read(mesh_t)
        engaged = not MESH_FORCED_OFF
        assert got == _read(single_t)
    else:
        from paimon_tpu.core.kv import KVBatch
        from paimon_tpu.data.batch import ColumnBatch
        from paimon_tpu.parallel.mesh_exec import MeshExecutor

        ex = _pair(tmp_warehouse, "huge_job", {"bucket": "1"})[0].store.merge_executor()
        n = 2048
        ids = rng.integers(0, 500, n)
        data = ColumnBatch.from_pydict(
            SCHEMA, {"id": ids.tolist(), "a": [float(i) for i in range(n)], "s": ["x"] * n}
        )
        kv = KVBatch.from_rows(data, 0)
        registry.reset()
        with MeshExecutor(key_axis_rows=1024).active():  # force the key-axis path
            h = ex.merge_async(kv, seq_ascending=True)
            merged = ex.merge_resolve(h)
        engaged = True  # an installed executor runs whatever the environment forces
        want = ex.merge(kv, seq_ascending=True)
        assert merged.data.to_pylist() == want.data.to_pylist()
        assert (merged.seq == want.seq).all()
    if engaged:
        assert mesh_metrics().counter("exchange_rows").count > 0, "oversized bucket stayed on the bucket axis"


def test_cpu_fallback_when_mesh_unusable(tmp_warehouse, rng, monkeypatch):
    """merge.engine=mesh on a 1-device / shard_map-less environment must
    degrade to the single-device path bit-identically and never touch the
    executor (the SNIPPETS pjit_with_cpu_fallback contract at the seam)."""
    from paimon_tpu.parallel import mesh_exec

    mesh_t, single_t = _pair(tmp_warehouse, "fb", {"bucket": "3"})
    for data in _rounds(rng, rounds=2, n=600):
        _write(mesh_t, data)
        _write(single_t, data)
    monkeypatch.setattr(mesh_exec, "mesh_available", lambda: False)
    with mesh_exec.maybe_mesh_exec(mesh_t.store.options) as ctx:
        assert ctx is None
    registry.reset()
    got = _read(mesh_t)
    assert mesh_metrics().counter("buckets_sharded").count == 0
    assert got == _read(single_t)


@pytest.mark.parametrize("buckets", [6, 1])
def test_feeder_streams_in_split_order(tmp_warehouse, rng, buckets):
    """batches() under the mesh engine emits per-split batches in plan order
    (the determinism the ConcatRecordReader contract requires), with the
    feeder wait metric populated. One bucket is a plan of a single data
    split: a round of one job, equal to the single-engine read."""
    mesh_t, single_t = _pair(tmp_warehouse, f"feed{buckets}", {"bucket": str(buckets), "write-only": "true"})
    for data in _rounds(rng, rounds=2, n=900):
        _write(mesh_t, data)
        _write(single_t, data)
    registry.reset()

    def batches(t):
        rb = t.new_read_builder()
        read = rb.new_read()
        plan = rb.new_scan().plan()
        assert (len(plan) == 1) == (buckets == 1)
        return [b.to_pylist() for b in read.batches(plan)]

    got, want = batches(mesh_t), batches(single_t)
    assert got == want
    if not MESH_FORCED_OFF:
        assert mesh_metrics().histogram("feeder_wait_ms").count > 0
        # every split was a job of the executor (both tables' where the engine is forced on)
        assert mesh_metrics().counter("buckets_sharded").count >= buckets


# ---------------------------------------------------------------------------
# satellite 1: global lane planning
# ---------------------------------------------------------------------------


def _shard_lanes(rng):
    """One bucket's rows in two device-range halves with deliberately
    different lane stats: half A spans 8 bits on lane 1, half B spans ~14
    bits at a different base — per-shard plans pack them differently."""
    n_half = 512
    a0 = rng.integers(100, 120, n_half).astype(np.uint32)
    a1 = rng.integers(0, 200, n_half).astype(np.uint32)
    b0 = rng.integers(100, 140, n_half).astype(np.uint32)
    b1 = rng.integers(9_000, 24_000, n_half).astype(np.uint32)
    # plant exact duplicate keys across the halves: a correct dedup must
    # collapse them, which requires cross-shard comparability
    dup = rng.integers(0, n_half, 64)
    b0[:64] = a0[dup]
    b1[:64] = a1[dup]
    lanes = np.stack(
        [np.concatenate([a0, b0]), np.concatenate([a1, b1])], axis=1
    ).astype(np.uint32)
    return lanes, n_half


def test_global_lane_plan_regression(rng):
    """The satellite-1 pin: per-shard LanePlans disagree on packed widths,
    and feeding per-shard-packed lanes through the key-axis distributed
    dedup produces a WRONG result (cross-shard duplicates survive because
    their packed codes differ); the global plan fixes it. This test fails if
    planning ever moves back inside the shard."""
    from paimon_tpu.ops.lanes import apply_plan, plan_lanes, plan_lanes_global
    from paimon_tpu.parallel.mesh_exec import _meshes, distributed_dedup_select

    lanes, n_half = _shard_lanes(rng)
    shards = [lanes[:n_half], lanes[n_half:]]
    plan_a, plan_b = (plan_lanes(s, enable_ovc=False) for s in shards)
    # the hazard is real: the shards genuinely plan different packings
    assert (plan_a.bits != plan_b.bits) or (plan_a.los != plan_b.los)

    # oracle: single-device dedup on the raw lanes (last duplicate wins)
    from paimon_tpu.core.mergefn import _numpy_dedup_select

    oracle = _numpy_dedup_select(lanes.copy(), None, compress=False)

    key_mesh = _meshes()[1]
    # global plan: stats reduced over both shards -> one comparable packing
    gplan = plan_lanes_global(shards)
    good = distributed_dedup_select(key_mesh, apply_plan(gplan, lanes))
    assert good.tolist() == oracle.tolist()

    # per-shard plans (the bug this PR removes): each half packed by its own
    # plan, then stacked — packed values are incomparable across shards, so
    # the distributed selection diverges from the oracle
    if plan_a.lanes_out == plan_b.lanes_out:
        bad_lanes = np.concatenate(
            [apply_plan(plan_a, shards[0]), apply_plan(plan_b, shards[1])]
        )
        bad = distributed_dedup_select(key_mesh, bad_lanes)
        assert bad.tolist() != oracle.tolist(), (
            "per-shard planning unexpectedly survived — the regression pin is dead"
        )


def test_plan_lanes_global_matches_stats_reduction(rng):
    """plan_lanes_global == plan_lanes_from_stats over the element-wise
    reduced stats, and applying it to any shard yields operands within the
    planned widths (the invariant the packing injectivity rests on)."""
    from paimon_tpu.ops.lanes import (
        apply_plan,
        lane_stats,
        plan_lanes_from_stats,
        plan_lanes_global,
    )

    shards = [
        rng.integers(0, 1 << 20, (200, 3)).astype(np.uint32),
        rng.integers(1 << 10, 1 << 24, (300, 3)).astype(np.uint32),
        np.empty((0, 3), dtype=np.uint32),  # empty shard contributes nothing
    ]
    gplan = plan_lanes_global(shards)
    los = np.minimum(*[lane_stats(s)[0] for s in shards[:2]])
    his = np.maximum(*[lane_stats(s)[1] for s in shards[:2]])
    assert gplan == plan_lanes_from_stats(3, los, his)
    for s in shards[:2]:
        packed = apply_plan(gplan, s)
        assert packed.shape == (len(s), gplan.lanes_out)


def test_mesh_metrics_breakdown(tmp_warehouse, rng):
    """The mesh{} group carries the full breakdown after a mesh scan."""
    mesh_t, _ = _pair(tmp_warehouse, "metrics", {"bucket": "4", "write-only": "true"})
    for data in _rounds(rng, rounds=2, n=800):
        _write(mesh_t, data)
    if MESH_FORCED_OFF:
        pytest.skip("merge.engine forced single: no mesh counters to assert")
    registry.reset()
    _read(mesh_t)
    g = mesh_metrics()
    assert g.counter("buckets_sharded").count >= 4
    assert g.counter("shards").count >= 1
    assert g.counter("pad_rows").count > 0
    assert g.histogram("device_busy_ms").count >= 1


# ---------------------------------------------------------------------------
# table operations through the mesh on a partitioned table: write flush,
# compaction rewrite and merge-read batch their per-bucket jobs into shard_map
# calls, and results match the single-device path (the TPU analog of the
# reference's engine-distributed execution: FlinkSinkBuilder.java:223
# topology, MergeTreeSplitGenerator.java:38 splits)
# ---------------------------------------------------------------------------

PT_SCHEMA = pt.RowType.of(("pt", pt.STRING()), ("id", pt.BIGINT()), ("v", pt.DOUBLE()), ("name", pt.STRING()))


@pytest.fixture
def two_tables(tmp_warehouse):
    """The same logical partitioned table twice: mesh-parallel and single-device."""
    cat = FileSystemCatalog(tmp_warehouse, commit_user="mesh")
    common = {"bucket": "4", "write-buffer.rows": "100000"}
    par = cat.create_table(
        "db.par", PT_SCHEMA, primary_keys=["pt", "id"], partition_keys=["pt"],
        options={**common, "merge.engine": "mesh"},
    )
    ser = cat.create_table(
        "db.ser", PT_SCHEMA, primary_keys=["pt", "id"], partition_keys=["pt"], options=common
    )
    return par, ser


def _dataset(rng, rounds=3, n=600):
    out = []
    for r in range(rounds):
        ids = rng.integers(0, 400, n)
        out.append(
            {
                "pt": [f"p{i % 2}" for i in ids],
                "id": ids.tolist(),
                "v": (ids * 1.0 + r * 1000).tolist(),
                "name": [f"r{r}-{i}" for i in ids],
            }
        )
    return out


def _canon(t):
    return sorted(_read(t))


def test_mesh_write_read_matches_single_device(two_tables, rng):
    par, ser = two_tables
    for data in _dataset(rng):
        _write(par, data)
        _write(ser, data)
    got, want = _canon(par), _canon(ser)
    assert got == want
    assert len(got) == len({(r[0], r[1]) for r in got})  # unique PKs


def test_mesh_compaction_matches_single_device(two_tables, rng):
    par, ser = two_tables
    for data in _dataset(rng, rounds=4, n=300):
        _write(par, data)
        _write(ser, data)
    for t in (par, ser):
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        w.compact(full=True)
        wb.new_commit().commit(w.prepare_commit())
    # full compaction leaves one top-level run per bucket and identical rows
    assert _canon(par) == _canon(ser)
    plan = par.store.new_scan().plan()
    for e in plan.entries:
        assert e.file.level == par.store.options.num_levels - 1


def test_mesh_read_batches_merges_into_one_call(two_tables, rng):
    """All buckets' merge-read jobs run in ONE batched shard_map call."""
    from paimon_tpu.parallel.mesh_exec import MeshExecutor

    par, _ = two_tables
    for data in _dataset(rng, rounds=2, n=400):
        _write(par, data)
    rb = par.new_read_builder()
    splits = rb.new_scan().plan()
    assert len(splits) >= 4  # 2 partitions x >=2 live buckets
    read = rb.new_read()
    mex = MeshExecutor()
    with mex.active():
        pending = [(s, read._dispatch(s)) for s in splits]
        out = [c() for _, c in pending]
        # one dedup batch served every bucket's merge (no per-bucket calls)
        assert mex.executed_batches == 1
    rows = sorted(r for b in out for r in b.to_pylist())
    assert rows == _canon(par)


def test_mesh_partial_update_and_aggregation(tmp_warehouse, rng):
    """Non-dedup engines route through the batched plan kernel."""
    cat = FileSystemCatalog(tmp_warehouse, commit_user="mesh2")
    schema = pt.RowType.of(("id", pt.BIGINT()), ("a", pt.DOUBLE()), ("b", pt.DOUBLE()))
    for engine, extra in (
        ("partial-update", {}),
        ("aggregation", {"fields.a.aggregate-function": "sum", "fields.b.aggregate-function": "max"}),
    ):
        par = cat.create_table(
            f"db.pu_par_{engine[:4]}", schema, primary_keys=["id"],
            options={"bucket": "2", "merge-engine": engine, "merge.engine": "mesh", **extra},
        )
        ser = cat.create_table(
            f"db.pu_ser_{engine[:4]}", schema, primary_keys=["id"],
            options={"bucket": "2", "merge-engine": engine, **extra},
        )
        for r in range(3):
            ids = rng.integers(0, 50, 120)
            data = {
                "id": ids.tolist(),
                "a": [float(i + r) for i in ids],
                "b": [None if (i + r) % 3 == 0 else float(i * r) for i in ids],
            }
            _write(par, data)
            _write(ser, data)
        assert _canon(par) == _canon(ser), engine


def test_distributed_dedup_select_oracle(rng):
    """Key-axis path: range-shuffled dedup over every device matches the
    host oracle, including input-order tie-breaks."""
    from paimon_tpu.parallel.mesh_exec import _meshes, distributed_dedup_select

    _, key_mesh = _meshes()
    n = 4096
    keys = rng.integers(0, 300, n).astype(np.uint32)
    lanes = keys.reshape(-1, 1)
    sel = distributed_dedup_select(key_mesh, lanes)
    oracle = {}
    for i, k in enumerate(keys.tolist()):
        oracle[k] = i  # stability: last occurrence wins
    assert sel.tolist() == [oracle[k] for k in sorted(oracle)]
    # with explicit seq lanes reversing arrival order
    seq = (n - 1 - np.arange(n)).astype(np.uint32).reshape(-1, 1)
    sel2 = distributed_dedup_select(key_mesh, lanes, seq)
    oracle2 = {}
    for i, k in enumerate(keys.tolist()):
        if k not in oracle2:
            oracle2[k] = i  # highest seq = first occurrence
    assert sel2.tolist() == [oracle2[k] for k in sorted(oracle2)]


def test_mesh_partial_update_sequence_groups(tmp_warehouse, rng):
    """Sequence groups under mesh execution (batched plan jobs + per-group
    device picks) must match the single-device result."""
    cat = FileSystemCatalog(tmp_warehouse, commit_user="meshsg")
    schema = pt.RowType.of(("id", pt.BIGINT()), ("g1_seq", pt.BIGINT()), ("a", pt.DOUBLE()), ("b", pt.DOUBLE()))
    opts = {
        "bucket": "2",
        "merge-engine": "partial-update",
        "fields.g1_seq.sequence-group": "a,b",
    }
    par = cat.create_table("db.sg_par", schema, primary_keys=["id"], options={**opts, "merge.engine": "mesh"})
    ser = cat.create_table("db.sg_ser", schema, primary_keys=["id"], options=opts)
    for r in range(3):
        ids = rng.integers(0, 40, 80)
        data = {
            "id": ids.tolist(),
            # group sequence occasionally goes BACKWARD: stale updates must lose
            "g1_seq": [int(v) for v in rng.integers(0, 100, 80)],
            "a": [None if i % 4 == 0 else float(r * 100 + i) for i in ids],
            "b": [float(r) if i % 3 else None for i in ids],
        }
        _write(par, data)
        _write(ser, data)
    assert _canon(par) == _canon(ser)


def test_mesh_read_all_writes_the_winners_into_its_result(tmp_warehouse, rng):
    """An 8-bucket table of the benchmark's 15 columns under merge.engine=mesh:
    every split's continuation runs on the reading thread in split order, so
    the gathers write the 11 fixed-width columns straight into read_all's one
    result and only the 4 STRING columns are joined; same rows as the
    single-device read, which finishes its splits on the pipeline's workers
    and copies every batch in."""
    names = ["id"] + [f"b{i}" for i in range(6)] + [f"d{i}" for i in range(4)] + [f"s{i}" for i in range(4)]
    schema = pt.RowType.of(*[(n, pt.BIGINT(False) if n == "id" else pt.STRING() if n[0] == "s" else pt.DOUBLE() if n[0] == "d" else pt.BIGINT())
                             for n in names])
    cat = FileSystemCatalog(tmp_warehouse, commit_user="mesh-sink")
    opts = {"bucket": "8", "write-only": "true"}
    mesh_t = cat.create_table("db.sink_mesh", schema, primary_keys=["id"], options={**opts, "merge.engine": "mesh"})
    single_t = cat.create_table("db.sink_single", schema, primary_keys=["id"], options=opts)
    for r in range(3):  # overlapping runs, so every bucket has to merge
        ids = rng.choice(4_000, 2_500, replace=False).astype(np.int64)
        data = {n: ids if n == "id" else np.array([f"{n}-{i % 13}-{r}" for i in ids], dtype=object) if n[0] == "s"
                else ids / 3 + r if n[0] == "d" else ids * 7 + r for n in names}
        _write(mesh_t, data)
        _write(single_t, data)

    def read(t):
        rb = t.new_read_builder()
        splits = rb.new_scan().plan()
        assert len(splits) == 8
        before = dict(registry.snapshot().get("read", {}))
        out = rb.new_read().read_all(splits)
        return out, {k: v - before.get(k, 0) for k, v in registry.snapshot()["read"].items()}

    got, counted = read(mesh_t)
    want, counted_single = read(single_t)
    assert got.to_pylist() == want.to_pylist() and got.num_rows > 2_500
    for n in names:
        assert got.column(n).null_count == 0 and (got.column(n)._values is None) == (n[0] == "s")
    assert (counted_single["rows_placed"], counted_single["rows_joined"]) == (0, 15 * want.num_rows)
    if not MESH_FORCED_OFF:
        assert (counted["rows_placed"], counted["rows_joined"]) == (11 * got.num_rows, 4 * got.num_rows)
