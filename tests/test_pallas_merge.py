"""Pallas merge engine parity: pallas (interpreted on the CPU, compiled by
Mosaic under PAIMON_TEST_PLATFORM=tpu) == xla-segmented == numpy oracle, bit
for bit, across seeds x key shapes x null rates x lane-compression on/off x
dict-domain on/off. The `scripts/verify.sh pallas` stage runs this file (plus the
merge-kernel and whole-store oracles) with PAIMON_TPU_SORT_ENGINE forced
pallas and then xla-segmented."""

import jax
import numpy as np
import pytest

import paimon_tpu.ops.pallas_kernels as pk
from paimon_tpu.core.mergefn import _numpy_dedup_select
from paimon_tpu.ops import merge as M
from paimon_tpu.ops.merge import merge_plan, sorted_segments


def _dedup_oracle(lanes: np.ndarray, seq_lanes: np.ndarray | None = None) -> np.ndarray:
    return _numpy_dedup_select(lanes, seq_lanes, compress=False)


def _rand_lanes(rng, n, shape):
    """Key-lane matrices covering the shapes the planner narrows/packs
    differently: single dense, two mixed-width, four wide, u16-range."""
    if shape == "one":
        return rng.integers(0, max(2, n // 2), (n, 1)).astype(np.uint32)
    if shape == "narrow":
        return rng.integers(0, 200, (n, 1)).astype(np.uint32)
    if shape == "two":
        a = rng.integers(0, 50, n).astype(np.uint32)
        b = rng.integers(0, 1 << 20, n).astype(np.uint32)
        return np.stack([a, b], axis=1)
    a = rng.integers(0, 9, n).astype(np.uint32)
    b = rng.integers(0, 3, n).astype(np.uint32)
    c = rng.integers(0, 1 << 30, n).astype(np.uint32)
    d = rng.integers(0, 100, n).astype(np.uint32)
    return np.stack([a, b, c, d], axis=1)


# ---------------------------------------------------------------------------
# kernel-level parity: dedup select
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("shape", ["one", "narrow", "two", "four"])
@pytest.mark.parametrize("compress", [False, True])
def test_dedup_parity_pallas_xla_numpy(seed, shape, compress):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(3, 2500))
    lanes = _rand_lanes(rng, n, shape)
    oracle = np.asarray(_dedup_oracle(lanes))
    xla = M.deduplicate_resolve(M.deduplicate_select_async(lanes, None, backend="xla", compress=compress))
    pallas = M.deduplicate_resolve(
        M.deduplicate_select_async(lanes, None, backend="pallas", compress=compress)
    )
    assert pallas.tolist() == xla.tolist() == oracle.tolist()


@pytest.mark.parametrize("seed", range(3))
def test_dedup_parity_with_seq_lanes(seed):
    rng = np.random.default_rng(100 + seed)
    n = int(rng.integers(10, 1500))
    lanes = _rand_lanes(rng, n, "two")
    seq = rng.permutation(n).astype(np.uint32).reshape(-1, 1)
    oracle = np.asarray(_dedup_oracle(lanes, seq))
    xla = M.deduplicate_resolve(M.deduplicate_select_async(lanes, seq, backend="xla", compress=False))
    pallas = M.deduplicate_resolve(
        M.deduplicate_select_async(lanes, seq, backend="pallas", compress=False)
    )
    assert pallas.tolist() == xla.tolist() == oracle.tolist()


@pytest.mark.parametrize("seed", range(3))
def test_sweep_tier_parity(seed):
    """The pallas engine keeps lax.sort and computes boundaries with the
    sweep kernel — the full sorted_segments contract, array for array."""
    rng = np.random.default_rng(200 + seed)
    n = int(rng.integers(5, 2000))
    lanes = _rand_lanes(rng, n, "two")
    m = M.pad_size(n)
    kl = np.full((2, m), 0xFFFFFFFF, dtype=np.uint32)
    kl[:, :n] = lanes.T
    pad = np.zeros(m, dtype=np.uint32)
    pad[n:] = 1

    def run(engine):
        @jax.jit
        def f(kl, pad):
            return sorted_segments(2, 0, kl, [], pad, engine=engine)

        return [np.asarray(x) for x in f(kl, pad)]

    for a, b in zip(run("xla"), run("pallas")):
        assert (a == b).all()


# ---------------------------------------------------------------------------
# merge_plan / partial-update / aggregate parity through the seam
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(3))
def test_merge_plan_parity(seed):
    rng = np.random.default_rng(300 + seed)
    n = int(rng.integers(3, 2000))
    lanes = _rand_lanes(rng, n, "two")
    seq = np.stack(
        [np.zeros(n, np.uint32), rng.permutation(n).astype(np.uint32)], axis=1
    )
    a = merge_plan(lanes, seq, compress=False, engine="xla")
    b = merge_plan(lanes, seq, compress=False, engine="pallas")
    assert (a.perm == b.perm).all()
    assert (a.seg_start == b.seg_start).all()
    assert (a.keep_last == b.keep_last).all()
    assert (a.seg_id == b.seg_id).all()
    assert a.n == b.n and a.m == b.m


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("null_rate", [0.0, 0.4])
def test_fused_partial_update_parity(seed, null_rate):
    from paimon_tpu.types import RowKind

    rng = np.random.default_rng(400 + seed)
    n = int(rng.integers(10, 1200))
    lanes = _rand_lanes(rng, n, "one")
    fv = rng.random((3, n)) >= null_rate
    kinds = rng.choice(
        [int(RowKind.INSERT), int(RowKind.UPDATE_AFTER), int(RowKind.DELETE)],
        size=n,
        p=[0.6, 0.3, 0.1],
    ).astype(np.uint8)
    outs = {}
    for engine in ("xla", "pallas"):
        outs[engine] = M.fused_partial_update(
            lanes, None, fv, kinds, remove_record_on_delete=True, compress=False, engine=engine
        )
    for a, b in zip(outs["xla"], outs["pallas"]):
        assert np.asarray(a).tolist() == np.asarray(b).tolist()


@pytest.mark.parametrize("seed", range(3))
def test_fused_aggregate_parity(seed):
    from paimon_tpu.data.batch import Column
    from paimon_tpu.ops import AggregateSpec
    from paimon_tpu.ops.aggregates import fused_aggregate
    from paimon_tpu.types import RowKind

    rng = np.random.default_rng(500 + seed)
    n = int(rng.integers(10, 1200))
    lanes = _rand_lanes(rng, n, "narrow")
    vals = rng.integers(-50, 50, n).astype(np.int64)
    valid = rng.random(n) >= 0.2
    cols = [Column(vals, valid), Column(np.abs(vals) + 1)]
    specs = [AggregateSpec("sum"), AggregateSpec("max")]
    kinds = np.full(n, int(RowKind.INSERT), dtype=np.uint8)
    outs = {}
    for engine in ("xla", "pallas"):
        agg, take = fused_aggregate(lanes, None, cols, specs, kinds, compress=False, engine=engine)
        outs[engine] = ([(c.values.tolist(), c.valid_mask().tolist()) for c in agg], take.tolist())
    assert outs["xla"] == outs["pallas"]


@pytest.mark.parametrize("seed", range(3))
def test_ovc_composes_with_pallas(seed):
    """PR 6 offset-value coding must ride through the pallas engine
    unchanged: run-sorted composite keys with compression on (the OVC
    qualifying shape) select identically under all three engines."""
    rng = np.random.default_rng(600 + seed)
    runs, per = 4, 400
    parts = []
    for _ in range(runs):
        r = np.stack(
            [
                np.sort(rng.integers(0, 1 << 24, per)).astype(np.uint32),
                rng.integers(0, 1 << 16, per).astype(np.uint32),
                rng.integers(0, 1 << 8, per).astype(np.uint32),
            ],
            axis=1,
        )
        r = r[np.lexsort([r[:, 2], r[:, 1], r[:, 0]])]
        parts.append(r)
    lanes = np.concatenate(parts)
    oracle = np.asarray(_numpy_dedup_select(lanes, None, compress=True))
    xla = M.deduplicate_resolve(M.deduplicate_select_async(lanes, None, backend="xla", compress=True))
    pallas = M.deduplicate_resolve(
        M.deduplicate_select_async(lanes, None, backend="pallas", compress=True)
    )
    assert pallas.tolist() == xla.tolist() == oracle.tolist()


# ---------------------------------------------------------------------------
# boundary-sweep shape contract (satellite: the m % 128 fix)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m", [1, 3, 127, 129, 200, 2047, 2049, 5000])
def test_keep_last_mask_non_multiple_sizes(m):
    """The old wrapper silently required m % 128 == 0 (grid = m // block
    truncated the tail); any m must now produce the exact boundary mask."""
    rng = np.random.default_rng(m)
    keys = np.sort(rng.integers(0, max(2, m // 3), m)).astype(np.uint32)
    pad = np.zeros(m, dtype=np.uint32)
    stacked = np.stack([pad, keys])
    out = np.asarray(pk.keep_last_mask(stacked, interpret=pk.pallas_interpret()))
    if m == 1:
        expect = np.ones(1, np.uint32)
    else:
        expect = np.concatenate([keys[1:] != keys[:-1], [True]]).astype(np.uint32)
    assert (out == expect).all()


def test_keep_last_mask_pad_contract():
    """mask_pad=True zeroes pad rows (legacy dedup mask); mask_pad=False is
    the raw sorted_segments keep_last where the pad segment closes too."""
    keys = np.array([1, 1, 2, 0, 0], dtype=np.uint32)  # 2 valid keys + pads
    pad = np.array([0, 0, 0, 1, 1], dtype=np.uint32)
    stacked = np.stack([pad, keys])
    masked = np.asarray(pk.keep_last_mask(stacked, interpret=pk.pallas_interpret(), mask_pad=True))
    raw = np.asarray(pk.keep_last_mask(stacked, interpret=pk.pallas_interpret(), mask_pad=False))
    assert masked.tolist() == [0, 1, 1, 0, 0]
    assert raw.tolist() == [0, 1, 1, 0, 1]


def test_note_dispatch_metrics():
    from paimon_tpu.metrics import registry

    with registry._lock:
        registry.groups.pop(("pallas", ()), None)
    pk.note_dispatch(1000)
    pk.note_dispatch(1 << 19)
    snap = registry.snapshot()["pallas"]
    assert snap["kernels_launched"] == 2
    assert snap["tiles"] == 1 + (1 << 19) // 2048


# ---------------------------------------------------------------------------
# table level: sort-engine x lane-compression x dict-domain matrix
# ---------------------------------------------------------------------------


def _build_matrix_table(tmp_warehouse, rng):
    from paimon_tpu.catalog import FileSystemCatalog
    from paimon_tpu.types import BIGINT, DOUBLE, RowType, STRING

    cat = FileSystemCatalog(tmp_warehouse, commit_user="pm")
    t = cat.create_table(
        "db.pm",
        RowType.of(
            ("k1", STRING(False)), ("k2", BIGINT(False)), ("v", DOUBLE()), ("tag", STRING())
        ),
        primary_keys=["k1", "k2"],
        options={"bucket": "1", "write-only": "true"},
    )
    for _ in range(3):
        n = 900
        k1 = np.array([f"user-{int(x):05d}" for x in rng.integers(0, 400, n)], dtype=object)
        k2 = rng.integers(0, 5, n).astype(np.int64)
        v = rng.random(n)
        tag = np.array(
            [None if rng.random() < 0.3 else f"t{int(x)}" for x in rng.integers(0, 8, n)],
            dtype=object,
        )
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        w.write({"k1": k1, "k2": k2, "v": v, "tag": tag})
        wb.new_commit().commit(w.prepare_commit())
    return t


def test_table_matrix_sort_engines(tmp_warehouse, rng):
    t = _build_matrix_table(tmp_warehouse, rng)
    results = {}
    for engine in ("xla-segmented", "pallas", "numpy"):
        for compress in ("true", "false"):
            for dd in ("true", "false"):
                tt = t.copy(
                    {
                        "sort-engine": engine,
                        "merge.lane-compression": compress,
                        "merge.dict-domain": dd,
                        "cache.data-file.max-memory-size": "0 b",
                    }
                )
                rb = tt.new_read_builder()
                out = rb.new_read().read_all(rb.new_scan().plan())
                results[(engine, compress, dd)] = out.to_pylist()
    ref = results[("xla-segmented", "true", "true")]
    assert len(ref) > 0
    for key, rows in results.items():
        assert rows == ref, f"divergent output for {key}"


def test_table_pallas_compaction_parity(tmp_warehouse):
    """Compaction rewrite inherits the seam: full-compact twin tables under
    sort-engine=pallas and xla-segmented and assert identical content."""
    outs = {}
    for engine in ("xla-segmented", "pallas"):
        sub = f"{tmp_warehouse}/{engine}"
        tt = _build_matrix_table(sub, np.random.default_rng(7)).copy(
            {"sort-engine": engine, "write-only": "false"}
        )
        wb = tt.new_batch_write_builder()
        w = wb.new_write()
        w.compact(full=True)
        wb.new_commit().commit(w.prepare_commit())
        rb = tt.new_read_builder()
        outs[engine] = rb.new_read().read_all(rb.new_scan().plan()).to_pylist()
    assert outs["pallas"] == outs["xla-segmented"]
    assert len(outs["pallas"]) > 0


def test_table_pallas_sort_compact_parity(tmp_warehouse):
    """Sort-compact's clustering sort inherits the seam too (append-only
    tables): zorder-rewrite twins and compare plan-order readback."""
    from paimon_tpu.catalog import FileSystemCatalog
    from paimon_tpu.table.sort_compact import sort_compact
    from paimon_tpu.types import BIGINT, DOUBLE, RowType

    outs = {}
    for engine in ("xla-segmented", "pallas"):
        rng_e = np.random.default_rng(11)
        cat = FileSystemCatalog(f"{tmp_warehouse}/{engine}", commit_user="sc")
        t = cat.create_table(
            "db.sc",
            RowType.of(("a", BIGINT()), ("b", BIGINT()), ("v", DOUBLE())),
            options={"bucket": "1", "sort-engine": engine},
        )
        for _ in range(2):
            n = 1500
            wb = t.new_batch_write_builder()
            w = wb.new_write()
            w.write(
                {
                    "a": rng_e.integers(0, 1 << 16, n),
                    "b": rng_e.integers(0, 1 << 16, n),
                    "v": rng_e.random(n),
                }
            )
            wb.new_commit().commit(w.prepare_commit())
        sort_compact(t, ["a", "b"], order="zorder")
        rb = t.new_read_builder()
        outs[engine] = rb.new_read().read_all(rb.new_scan().plan()).to_pylist()
    assert outs["pallas"] == outs["xla-segmented"]
    assert len(outs["pallas"]) == 3000
