"""Device-side skew-aware join subsystem (ISSUE 12, ops/join + SQL JOIN +
vectorized lookups).

The contract: every join result is BIT-IDENTICAL to an independent host
oracle (a dict-based nested probe, cross-checked against pandas.merge at
the SQL level) — across seeds, key skew, null rates, dict/non-dict key
columns, lane-compression on/off, engines (numpy / xla) and
partitioned skew-split execution — while dict-backed keys actually match
in the code domain (join{code_domain_joins} > 0, zero string
materialization on the matched path) and one hot key never serializes a
partition (the pinned 50%-skew regression)."""

import numpy as np
import pytest

from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.data.batch import Column, ColumnBatch
from paimon_tpu.metrics import join_metrics, registry
from paimon_tpu.ops.join import JoinError, JoinIndex, join_batches, materialize_join
from paimon_tpu.types import BIGINT, DATE, DOUBLE, INT, STRING, RowType


def oracle_pairs(left_keys, right_keys, how="inner"):
    """Independent nested-probe oracle: probe-major pairs, build rows
    ascending within each probe row; NULL (None) keys never match."""
    pos: dict = {}
    for j, k in enumerate(right_keys):
        if k is not None and (not isinstance(k, tuple) or None not in k):
            pos.setdefault(k, []).append(j)
    lt, rt = [], []
    for i, k in enumerate(left_keys):
        matches = (
            pos.get(k, [])
            if k is not None and (not isinstance(k, tuple) or None not in k)
            else []
        )
        if matches:
            for j in matches:
                lt.append(i)
                rt.append(j)
        elif how == "left":
            lt.append(i)
            rt.append(-1)
    return np.asarray(lt, dtype=np.int64), np.asarray(rt, dtype=np.int64)


def keys_of(batch, names):
    cols = [batch.column(n).to_pylist() for n in names]
    if len(cols) == 1:
        return cols[0]
    return [None if any(v is None for v in row) else tuple(row) for row in zip(*cols)]


def assert_join_matches_oracle(left, right, lkeys, rkeys, how, **kw):
    res = join_batches(left, right, lkeys, rkeys, how=how, **kw)
    olt, ort = oracle_pairs(keys_of(left, lkeys), keys_of(right, rkeys), how)
    np.testing.assert_array_equal(res.left_take, olt)
    np.testing.assert_array_equal(res.right_take, ort)
    return res


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------

SKEWS = {
    "uniform": lambda rng, n, dom: rng.integers(0, dom, n),
    "zipfish": lambda rng, n, dom: np.minimum(
        (rng.pareto(1.2, n) * dom / 8).astype(np.int64), dom - 1
    ),
    "hot50": lambda rng, n, dom: np.where(
        rng.random(n) < 0.5, 7, rng.integers(0, dom, n)
    ),
}


@pytest.mark.parametrize("engine", ["numpy", "xla"])
@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("skew", sorted(SKEWS))
def test_single_int_key_parity(engine, how, skew):
    rng = np.random.default_rng(hash((engine, how, skew)) % (1 << 16))
    n_l, n_r, dom = 3000, 500, 700
    lk = SKEWS[skew](rng, n_l, dom).astype(np.int64)
    rk = rng.choice(dom, n_r, replace=False).astype(np.int64)
    left = ColumnBatch.from_pydict(
        RowType.of(("k", BIGINT()), ("v", DOUBLE())), {"k": lk.tolist(), "v": (lk * 0.5).tolist()}
    )
    right = ColumnBatch.from_pydict(
        RowType.of(("id", BIGINT()), ("name", STRING())),
        {"id": rk.tolist(), "name": [f"n{int(x)}" for x in rk]},
    )
    assert_join_matches_oracle(left, right, ["k"], ["id"], how, engine=engine)


@pytest.mark.parametrize("engine", ["numpy", "xla"])
@pytest.mark.parametrize("null_rate", [0.0, 0.25])
def test_composite_string_int_key_parity(engine, null_rate):
    rng = np.random.default_rng(hash((engine, null_rate)) % (1 << 16))
    n_l, n_r = 1500, 400
    schema_l = RowType.of(("s", STRING()), ("k", INT()), ("v", DOUBLE()))
    schema_r = RowType.of(("s", STRING()), ("id", INT()), ("w", BIGINT()))

    def col(n, dom):
        return [
            None if rng.random() < null_rate else f"g{int(x)}"
            for x in rng.integers(0, dom, n)
        ]

    left = ColumnBatch.from_pydict(
        schema_l,
        {"s": col(n_l, 6), "k": rng.integers(0, 40, n_l).tolist(), "v": [0.5] * n_l},
    )
    right = ColumnBatch.from_pydict(
        schema_r,
        {"s": col(n_r, 9), "id": rng.integers(0, 40, n_r).tolist(), "w": [1] * n_r},
    )
    for how in ("inner", "left"):
        res = assert_join_matches_oracle(left, right, ["s", "k"], ["s", "id"], how, engine=engine)
    assert res.stats["algorithm"] in ("hash", "sort-merge")


@pytest.mark.parametrize("compress", ["1", "0"])
def test_lane_compression_on_off_identical(monkeypatch, compress):
    monkeypatch.setenv("PAIMON_TPU_LANE_COMPRESSION", compress)
    rng = np.random.default_rng(11)
    n_l, n_r = 2000, 300
    left = ColumnBatch.from_pydict(
        RowType.of(("a", BIGINT()), ("b", INT())),
        {"a": rng.integers(0, 50, n_l).tolist(), "b": rng.integers(0, 9, n_l).tolist()},
    )
    right = ColumnBatch.from_pydict(
        RowType.of(("a", BIGINT()), ("b", INT())),
        {"a": rng.integers(0, 50, n_r).tolist(), "b": rng.integers(0, 9, n_r).tolist()},
    )
    for engine in ("numpy", "xla"):
        assert_join_matches_oracle(left, right, ["a", "b"], ["a", "b"], "inner", engine=engine)


def test_skew_split_pinned_regression():
    """One key holds 50% of the probe rows: the partitioner must SPLIT it
    (join{skew_keys, skew_split_rows}) across every partition, and the
    output must stay bit-identical to the unpartitioned oracle."""
    rng = np.random.default_rng(5)
    n_l, n_r = 8000, 600
    lk = rng.integers(0, 800, n_l)
    lk[: n_l // 2] = 13
    rng.shuffle(lk)
    rk = rng.choice(800, n_r, replace=False)
    left = ColumnBatch.from_pydict(RowType.of(("k", BIGINT()),), {"k": lk.tolist()})
    right = ColumnBatch.from_pydict(RowType.of(("id", BIGINT()),), {"id": rk.tolist()})
    registry.reset()
    res = assert_join_matches_oracle(
        left, right, ["k"], ["id"], "inner", options={"join.partitions": "4"}
    )
    assert res.stats["partitions"] == 4
    assert res.stats["skew_keys"] >= 1
    assert res.stats["skew_split_rows"] >= n_l // 2
    g = join_metrics()
    assert g.counter("skew_keys").count >= 1
    assert g.counter("skew_split_rows").count >= n_l // 2
    # and the split spread the hot key: each partition saw some of its rows
    # (round-robin deal), which the bit-identical output already proves


def test_partitioned_left_join_parity():
    rng = np.random.default_rng(17)
    n_l, n_r = 5000, 600
    lk = SKEWS["hot50"](rng, n_l, 900).astype(np.int64)
    rk = rng.choice(900, n_r, replace=False).astype(np.int64)
    left = ColumnBatch.from_pydict(RowType.of(("k", BIGINT()),), {"k": lk.tolist()})
    right = ColumnBatch.from_pydict(RowType.of(("id", BIGINT()),), {"id": rk.tolist()})
    for engine in ("numpy", "xla"):
        assert_join_matches_oracle(
            left, right, ["k"], ["id"], "left",
            options={"join.partitions": "3"}, engine=engine,
        )


def test_all_equal_keys_cross_product():
    left = ColumnBatch.from_pydict(RowType.of(("k", BIGINT()),), {"k": [7, 7, 7]})
    right = ColumnBatch.from_pydict(RowType.of(("id", BIGINT()),), {"id": [7, 7]})
    res = assert_join_matches_oracle(left, right, ["k"], ["id"], "inner")
    assert res.num_rows == 6


def test_empty_sides():
    left = ColumnBatch.from_pydict(RowType.of(("k", BIGINT()),), {"k": [1, 2]})
    empty = ColumnBatch.from_pydict(RowType.of(("id", BIGINT()),), {"id": []})
    assert join_batches(left, empty, ["k"], ["id"], how="inner").num_rows == 0
    res = join_batches(left, empty, ["k"], ["id"], how="left")
    np.testing.assert_array_equal(res.right_take, [-1, -1])
    assert join_batches(empty.rename(RowType.of(("k", BIGINT()),)), left.rename(RowType.of(("id", BIGINT()),)), ["k"], ["id"]).num_rows == 0


def test_null_keys_never_match():
    left = ColumnBatch.from_pydict(RowType.of(("s", STRING()),), {"s": ["a", None, "b", None]})
    right = ColumnBatch.from_pydict(RowType.of(("s", STRING()),), {"s": [None, "a", "a"]})
    res = assert_join_matches_oracle(left, right, ["s"], ["s"], "inner")
    assert res.num_rows == 2  # "a" matches twice; None rows never
    res = assert_join_matches_oracle(left, right, ["s"], ["s"], "left")
    assert (res.right_take < 0).sum() == 3  # both None rows + "b" unmatched


def test_key_type_mismatch_raises():
    left = ColumnBatch.from_pydict(RowType.of(("k", BIGINT()),), {"k": [1]})
    right = ColumnBatch.from_pydict(RowType.of(("k", STRING()),), {"k": ["x"]})
    with pytest.raises(JoinError):
        join_batches(left, right, ["k"], ["k"])


# ---------------------------------------------------------------------------
# code-domain joins
# ---------------------------------------------------------------------------


def _coded_column(rng, n, dom, prefix):
    vals = np.array([f"{prefix}{int(x):04d}" for x in rng.integers(0, dom, n)], dtype=object)
    pool = np.unique(vals)
    codes = np.searchsorted(pool, vals).astype(np.uint32)
    return Column.from_codes(pool, codes), vals


def test_code_domain_join_zero_string_materialization():
    rng = np.random.default_rng(23)
    n_l, n_r = 4000, 700
    lc, lvals = _coded_column(rng, n_l, 300, "d")
    rc, rvals = _coded_column(rng, n_r, 450, "d")
    left = ColumnBatch(RowType.of(("s", STRING()), ("v", DOUBLE())), {"s": lc, "v": Column(np.ones(n_l))})
    right = ColumnBatch(RowType.of(("s", STRING()), ("w", DOUBLE())), {"s": rc, "w": Column(np.ones(n_r))})
    registry.reset()
    res = join_batches(left, right, ["s"], ["s"], how="inner")
    olt, ort = oracle_pairs(lvals.tolist(), rvals.tolist(), "inner")
    np.testing.assert_array_equal(res.left_take, olt)
    np.testing.assert_array_equal(res.right_take, ort)
    assert res.stats["code_domain_cols"] == 1
    assert join_metrics().counter("code_domain_joins").count == 1
    out = materialize_join(left, right, res, [("s", "s"), ("v", "v")], [("w", "w")])
    # the matched path never expanded a single string: the output key column
    # is still code-backed and dict{fallback_expanded} stayed at zero
    assert out.column("s").is_code_backed
    from paimon_tpu.metrics import dict_metrics

    assert dict_metrics().counter("fallback_expanded").count == 0


def test_code_domain_pool_limit_falls_back(monkeypatch):
    monkeypatch.setenv("PAIMON_TPU_DICT_POOL_LIMIT", "8")
    rng = np.random.default_rng(29)
    lc, lvals = _coded_column(rng, 500, 40, "d")
    rc, rvals = _coded_column(rng, 200, 40, "d")
    left = ColumnBatch(RowType.of(("s", STRING()),), {"s": lc})
    right = ColumnBatch(RowType.of(("s", STRING()),), {"s": rc})
    res = join_batches(left, right, ["s"], ["s"])
    assert res.stats["code_domain_cols"] == 0  # expanded fallback, still exact
    olt, ort = oracle_pairs(lvals.tolist(), rvals.tolist(), "inner")
    np.testing.assert_array_equal(res.left_take, olt)
    np.testing.assert_array_equal(res.right_take, ort)


def test_fixed_width_code_domain_table_join(tmp_warehouse):
    """ISSUE 12 satellite: int/date dictionary columns read code-backed
    (native decoder) join in the code domain — bit-identical to the
    expanded read."""
    cat = FileSystemCatalog(tmp_warehouse, commit_user="fw")
    rt = RowType.of(("k", BIGINT(False)), ("cat", INT()), ("d", DATE()), ("v", DOUBLE()))
    t = cat.create_table(
        "db.fw", rt, primary_keys=["k"],
        options={"bucket": "1", "format.parquet.decoder": "native"},
    )
    rng = np.random.default_rng(31)
    wb = t.new_batch_write_builder()
    w = wb.new_write()
    ids = np.arange(1200, dtype=np.int64)
    w.write({
        "k": ids, "cat": (ids % 11).astype(np.int32),
        "d": (ids % 25).astype(np.int32), "v": ids * 0.25,
    })
    wb.new_commit().commit(w.prepare_commit())

    def read(dd):
        t2 = t.copy({"merge.dict-domain": dd})
        rb = t2.new_read_builder()
        return rb.new_read().read_all(rb.new_scan().plan())

    on, off = read("true"), read("false")
    assert on.column("cat").is_code_backed  # the reader delivered codes
    assert on.column("cat").dict_cache[0].dtype == np.dtype(np.int32)
    # parity AFTER the code-backed checks: to_pylist expands lazily in place
    assert on.to_pylist() == off.to_pylist()
    on = read("true")  # fresh code-backed batch for the join below
    dim = ColumnBatch.from_pydict(
        RowType.of(("cid", INT()), ("label", STRING())),
        {"cid": list(range(11)), "label": [f"c{i}" for i in range(11)]},
    )
    res_on = join_batches(on, dim, ["cat"], ["cid"])
    res_off = join_batches(off, dim, ["cat"], ["cid"])
    np.testing.assert_array_equal(res_on.left_take, res_off.left_take)
    np.testing.assert_array_equal(res_on.right_take, res_off.right_take)


# ---------------------------------------------------------------------------
# JoinIndex (cached build side / lookup tier)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("keys", [["id"], ["s"], ["s", "id"]])
def test_join_index_probe_parity(keys):
    rng = np.random.default_rng(37)
    n_b, n_p = 600, 2500
    build = ColumnBatch.from_pydict(
        RowType.of(("id", BIGINT()), ("s", STRING()), ("v", DOUBLE())),
        {
            "id": rng.integers(0, 200, n_b).tolist(),
            "s": [f"g{int(x)}" for x in rng.integers(0, 30, n_b)],
            "v": [1.0] * n_b,
        },
    )
    probe = ColumnBatch.from_pydict(
        RowType.of(("id", BIGINT()), ("s", STRING())),
        {
            # half the probe values fall OUTSIDE the build domain: the
            # present-mask must kill them exactly (no false matches)
            "id": rng.integers(0, 400, n_p).tolist(),
            "s": [f"g{int(x)}" for x in rng.integers(0, 60, n_p)],
        },
    )
    idx = JoinIndex(build, keys)
    for how in ("inner", "left"):
        res = idx.probe(probe, keys, how=how)
        olt, ort = oracle_pairs(keys_of(probe, keys), keys_of(build, keys), how)
        np.testing.assert_array_equal(res.left_take, olt)
        np.testing.assert_array_equal(res.right_take, ort)


def test_join_index_wide_key_falls_back():
    rng = np.random.default_rng(41)
    n = 300
    schema = RowType.of(("a", BIGINT()), ("b", BIGINT()), ("c", BIGINT()), ("s", STRING()))
    data = {
        "a": rng.integers(0, 1 << 40, n).tolist(),
        "b": rng.integers(0, 1 << 40, n).tolist(),
        "c": rng.integers(0, 1 << 40, n).tolist(),
        "s": [f"x{int(v)}" for v in rng.integers(0, 50, n)],
    }
    build = ColumnBatch.from_pydict(schema, data)
    idx = JoinIndex(build, ["a", "b", "c", "s"])
    assert idx.wide
    probe = build.slice(0, 50)
    res = idx.probe(probe, ["a", "b", "c", "s"], how="inner")
    olt, ort = oracle_pairs(
        keys_of(probe, ["a", "b", "c", "s"]), keys_of(build, ["a", "b", "c", "s"]), "inner"
    )
    np.testing.assert_array_equal(res.left_take, olt)
    np.testing.assert_array_equal(res.right_take, ort)


def test_join_index_null_and_empty_build():
    build = ColumnBatch.from_pydict(RowType.of(("s", STRING()),), {"s": [None, None]})
    idx = JoinIndex(build, ["s"])
    probe = ColumnBatch.from_pydict(RowType.of(("s", STRING()),), {"s": ["a", None]})
    res = idx.probe(probe, ["s"], how="left")
    np.testing.assert_array_equal(res.right_take, [-1, -1])
    empty = ColumnBatch.from_pydict(RowType.of(("s", STRING()),), {"s": []})
    idx2 = JoinIndex(empty, ["s"])
    assert idx2.probe(probe, ["s"], how="inner").num_rows == 0


# ---------------------------------------------------------------------------
# vectorized lookup tables
# ---------------------------------------------------------------------------


def _dim_table(tmp_warehouse, name="db.dim", n=300):
    cat = FileSystemCatalog(tmp_warehouse, commit_user="lkp")
    t = cat.create_table(
        name,
        RowType.of(("id", BIGINT(False)), ("name", STRING()), ("grp", STRING())),
        primary_keys=["id"],
        options={"bucket": "1"},
    )
    wb = t.new_batch_write_builder()
    w = wb.new_write()
    w.write({
        "id": np.arange(n, dtype=np.int64),
        "name": [f"n{i}" for i in range(n)],
        "grp": [f"g{i % 7}" for i in range(n)],
    })
    wb.new_commit().commit(w.prepare_commit())
    return t


def test_scalar_get_is_thin_wrapper_with_parity(tmp_warehouse):
    from paimon_tpu.lookup.tables import FullCacheLookupTable

    t = _dim_table(tmp_warehouse)
    primary = FullCacheLookupTable(t)
    secondary = FullCacheLookupTable(t, join_keys=["grp"])
    for k in [(0,), (123,), (299,), (9999,)]:
        assert primary.get(k) == primary._legacy_get(k)
    for k in [("g0",), ("g6",), ("nope",)]:
        assert secondary.get(k) == secondary._legacy_get(k)


def test_get_batch_vectorized_and_refresh_invalidation(tmp_warehouse):
    from paimon_tpu.lookup.tables import FullCacheLookupTable

    t = _dim_table(tmp_warehouse)
    lt = FullCacheLookupTable(t)
    batch, lidx = lt.get_batch([(5,), (700,), (9,)])
    assert batch.to_pylist() == [(5, "n5", "g5"), (9, "n9", "g2")]
    np.testing.assert_array_equal(lidx, [0, 2])
    # upsert a row, refresh: the index must rebuild
    wb = t.new_batch_write_builder()
    w = wb.new_write()
    w.write({"id": [5], "name": ["CHANGED"], "grp": ["g5"]})
    wb.new_commit().commit(w.prepare_commit())
    assert lt.refresh() > 0
    batch, _ = lt.get_batch([(5,)])
    assert batch.to_pylist() == [(5, "CHANGED", "g5")]


def test_lookup_join_enrichment_matches_pandas(tmp_warehouse):
    import pandas as pd

    from paimon_tpu.lookup.tables import FullCacheLookupTable, lookup_join

    t = _dim_table(tmp_warehouse)
    lt = FullCacheLookupTable(t)
    rng = np.random.default_rng(43)
    probe = ColumnBatch.from_pydict(
        RowType.of(("id", BIGINT()), ("x", DOUBLE())),
        {"id": rng.integers(0, 450, 1000).tolist(), "x": rng.random(1000).tolist()},
    )
    out = lookup_join(lt, probe)
    assert out.schema.field_names == ["id", "x", "id_lookup", "name", "grp"]
    pdf = pd.DataFrame(probe.to_pydict())
    ddf = pd.DataFrame(lt.state_batch().to_pydict())
    exp = pdf.merge(ddf, left_on="id", right_on="id", how="left", suffixes=("", "_r"))
    got = out.to_pydict()
    assert got["id"] == exp["id"].tolist()
    assert [v if v is not None else None for v in got["name"]] == [
        None if isinstance(v, float) and np.isnan(v) else v for v in exp["name"].tolist()
    ]


def test_no_pk_multimap_get_batch(tmp_warehouse):
    from paimon_tpu.lookup.tables import FullCacheLookupTable

    cat = FileSystemCatalog(tmp_warehouse, commit_user="lkp")
    t = cat.create_table(
        "db.app", RowType.of(("k", BIGINT()), ("v", STRING())), options={"bucket": "1"}
    )
    wb = t.new_batch_write_builder()
    w = wb.new_write()
    w.write({"k": [1, 2, 1, 3, 1], "v": ["a", "b", "c", "d", "e"]})
    wb.new_commit().commit(w.prepare_commit())
    lt = FullCacheLookupTable(t, join_keys=["k"])
    assert lt.get((1,)) == lt._legacy_get((1,)) == [(1, "a"), (1, "c"), (1, "e")]
    batch, lidx = lt.get_batch([(3,), (1,)])
    assert batch.to_pylist() == [(3, "d"), (1, "a"), (1, "c"), (1, "e")]
    np.testing.assert_array_equal(lidx, [0, 1, 1, 1])


# ---------------------------------------------------------------------------
# SQL JOIN end to end
# ---------------------------------------------------------------------------


@pytest.fixture
def star(tmp_warehouse):
    cat = FileSystemCatalog(tmp_warehouse, commit_user="sql")
    fact = cat.create_table(
        "db.fact",
        RowType.of(("id", BIGINT(False)), ("cust", BIGINT()), ("amount", DOUBLE()), ("qty", BIGINT())),
        primary_keys=["id"],
        options={"bucket": "1"},
    )
    dim = cat.create_table(
        "db.dim",
        RowType.of(("cid", BIGINT(False)), ("name", STRING()), ("region", STRING())),
        primary_keys=["cid"],
        options={"bucket": "1"},
    )
    rng = np.random.default_rng(7)
    n = 3000
    wb = fact.new_batch_write_builder()
    w = wb.new_write()
    w.write({
        "id": np.arange(n, dtype=np.int64),
        "cust": rng.integers(0, 140, n),  # 100..139 have no dim row
        "amount": rng.random(n).round(4),
        "qty": rng.integers(1, 5, n),
    })
    wb.new_commit().commit(w.prepare_commit())
    wb = dim.new_batch_write_builder()
    w = wb.new_write()
    w.write({
        "cid": np.arange(100, dtype=np.int64),
        "name": [f"c{i:03d}" for i in range(100)],
        "region": [["EU", "US", "APAC"][i % 3] for i in range(100)],
    })
    wb.new_commit().commit(w.prepare_commit())
    return cat, fact, dim


def _frames(fact, dim):
    import pandas as pd

    rb = fact.new_read_builder()
    fdf = pd.DataFrame(rb.new_read().read_all(rb.new_scan().plan()).to_pydict())
    rb = dim.new_read_builder()
    ddf = pd.DataFrame(rb.new_read().read_all(rb.new_scan().plan()).to_pydict())
    return fdf, ddf


def test_sql_inner_join_matches_pandas(star):
    from paimon_tpu.sql import query

    cat, fact, dim = star
    out = query(
        cat,
        "SELECT f.id, d.name, f.amount FROM db.fact f JOIN db.dim d ON f.cust = d.cid ORDER BY f.id",
    )
    fdf, ddf = _frames(fact, dim)
    exp = fdf.merge(ddf, left_on="cust", right_on="cid", how="inner").sort_values("id")
    assert out.num_rows == len(exp)
    assert out.to_pydict()["id"] == exp["id"].tolist()
    assert out.to_pydict()["name"] == exp["name"].tolist()


def test_sql_left_join_and_residual_where(star):
    from paimon_tpu.sql import query

    cat, fact, dim = star
    out = query(
        cat,
        "SELECT count(*) FROM db.fact f LEFT JOIN db.dim d ON f.cust = d.cid WHERE d.name IS NULL",
    )
    fdf, ddf = _frames(fact, dim)
    exp = fdf.merge(ddf, left_on="cust", right_on="cid", how="left")
    assert out.to_pylist()[0][0] == int(exp["name"].isna().sum())


def test_sql_join_group_by_and_pushdown(star):
    from paimon_tpu.sql import query

    cat, fact, dim = star
    out = query(
        cat,
        "SELECT region, count(*), sum(amount) FROM db.fact f JOIN db.dim d ON f.cust = d.cid "
        "WHERE region = 'EU' AND f.qty >= 2 GROUP BY region",
    )
    fdf, ddf = _frames(fact, dim)
    exp = fdf[fdf.qty >= 2].merge(ddf[ddf.region == "EU"], left_on="cust", right_on="cid")
    (row,) = out.to_pylist()
    assert row[0] == "EU" and row[1] == len(exp)
    assert abs(row[2] - exp["amount"].sum()) < 1e-9


def test_sql_join_star_and_ambiguity(star):
    from paimon_tpu.sql import query
    from paimon_tpu.sql.select import QueryError

    cat, _, _ = star
    out = query(cat, "SELECT * FROM db.fact f JOIN db.dim d ON f.cust = d.cid LIMIT 3")
    assert out.schema.field_names == ["id", "cust", "amount", "qty", "cid", "name", "region"]
    # a column present in both sides must be qualified
    with pytest.raises(QueryError):
        query(cat, "SELECT name FROM db.fact f JOIN db.fact g ON f.id = g.id")
    with pytest.raises(QueryError):
        query(cat, "SELECT f.id FROM db.fact f JOIN db.dim d ON f.cust < d.cid")


def test_sql_join_small_side_prunes_big_scan(star):
    """The dimension filter shrinks the fact-side scan: the planner pushes
    the small side's key set onto the big side as an IN predicate, so the
    fact read returns only prunable-matching rows (validated by result
    parity; the pushdown itself is observable through the join metrics'
    probe row count)."""
    from paimon_tpu.sql import query

    cat, fact, dim = star
    registry.reset()
    out = query(
        cat,
        "SELECT f.id FROM db.fact f JOIN db.dim d ON f.cust = d.cid WHERE d.region = 'APAC' ORDER BY f.id",
    )
    fdf, ddf = _frames(fact, dim)
    exp = fdf.merge(ddf[ddf.region == "APAC"], left_on="cust", right_on="cid")
    assert out.to_pydict()["id"] == sorted(exp["id"].tolist())
    probed = join_metrics().counter("rows_probed").count
    # the IN pushdown pre-filtered the fact rows to (close to) the matched
    # set: far fewer than the full 3000-row fact table reached the kernel
    assert probed <= len(exp)


def test_sql_join_under_mesh_and_dict_domain(star, monkeypatch):
    from paimon_tpu.sql import query

    cat, fact, dim = star
    base = query(
        cat,
        "SELECT f.id, d.region FROM db.fact f JOIN db.dim d ON f.cust = d.cid ORDER BY f.id LIMIT 50",
    ).to_pylist()
    monkeypatch.setenv("PAIMON_TPU_MERGE_ENGINE", "mesh")
    monkeypatch.setenv("PAIMON_TPU_DICT_DOMAIN", "1")
    got = query(
        cat,
        "SELECT f.id, d.region FROM db.fact f JOIN db.dim d ON f.cust = d.cid ORDER BY f.id LIMIT 50",
    ).to_pylist()
    assert got == base


def test_sql_join_multi_key_and_aliases_defaulted(tmp_warehouse):
    from paimon_tpu.sql import query

    cat = FileSystemCatalog(tmp_warehouse, commit_user="sql")
    a = cat.create_table(
        "db.a", RowType.of(("g", STRING(False)), ("n", BIGINT(False)), ("v", DOUBLE())),
        primary_keys=["g", "n"], options={"bucket": "1"},
    )
    b = cat.create_table(
        "db.b", RowType.of(("g", STRING(False)), ("n", BIGINT(False)), ("w", DOUBLE())),
        primary_keys=["g", "n"], options={"bucket": "1"},
    )
    rng = np.random.default_rng(3)
    for t, col in ((a, "v"), (b, "w")):
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        n = 400
        w.write({
            "g": [f"g{int(x)}" for x in rng.integers(0, 5, n)],
            "n": rng.integers(0, 50, n),
            col: rng.random(n),
        })
        wb.new_commit().commit(w.prepare_commit())
    out = query(
        cat, "SELECT a.g, a.n, v, w FROM db.a JOIN db.b ON a.g = b.g AND a.n = b.n ORDER BY a.g, a.n"
    )
    import pandas as pd

    rb = a.new_read_builder()
    adf = pd.DataFrame(rb.new_read().read_all(rb.new_scan().plan()).to_pydict())
    rb = b.new_read_builder()
    bdf = pd.DataFrame(rb.new_read().read_all(rb.new_scan().plan()).to_pydict())
    exp = adf.merge(bdf, on=["g", "n"], how="inner").sort_values(["g", "n"])
    # g/n exist in both tables: the output labels them alias-qualified
    assert out.schema.field_names == ["a.g", "a.n", "v", "w"]
    assert out.to_pydict()["a.g"] == exp["g"].tolist()
    assert out.to_pydict()["v"] == exp["v"].tolist()


# ---------------------------------------------------------------------------
# randomized cross-dimension oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(4))
def test_randomized_join_oracle(seed, monkeypatch):
    """seeds x skew x null-rate x dict/non-dict x engine x how x partitions:
    every combination bit-identical to the nested-probe oracle."""
    rng = np.random.default_rng(seed)
    monkeypatch.setenv(
        "PAIMON_TPU_LANE_COMPRESSION", "1" if seed % 2 == 0 else "0"
    )
    n_l = int(rng.integers(50, 4000))
    n_r = int(rng.integers(10, 800))
    dom = int(rng.integers(5, 500))
    null_rate = float(rng.choice([0.0, 0.1, 0.4]))
    skew = rng.choice(sorted(SKEWS))
    dict_backed = bool(rng.integers(0, 2))
    lk = SKEWS[skew](rng, n_l, dom)
    rk = rng.integers(0, dom, n_r)

    def scol(keys, n):
        vals = np.array(
            [None if rng.random() < null_rate else f"s{int(x):04d}" for x in keys],
            dtype=object,
        )
        if not dict_backed:
            return Column.from_pylist(vals, STRING()), vals
        present = np.array([v for v in vals if v is not None], dtype=object)
        pool = np.unique(present) if len(present) else np.empty(0, dtype=object)
        validity = np.array([v is not None for v in vals], dtype=bool)
        codes = np.zeros(n, dtype=np.uint32)
        if len(pool):
            codes[validity] = np.searchsorted(pool, present).astype(np.uint32)
        return Column.from_codes(pool, codes, None if validity.all() else validity), vals

    lc, lvals = scol(lk, n_l)
    rc, rvals = scol(rk, n_r)
    left = ColumnBatch(RowType.of(("s", STRING()),), {"s": lc})
    right = ColumnBatch(RowType.of(("s", STRING()),), {"s": rc})
    how = "left" if seed % 2 else "inner"
    engine = "numpy" if seed % 3 == 0 else "xla"
    parts = str(int(rng.integers(1, 5)))
    res = join_batches(
        left, right, ["s"], ["s"], how=how,
        options={"join.partitions": parts}, engine=engine,
    )
    olt, ort = oracle_pairs(lvals.tolist(), rvals.tolist(), how)
    np.testing.assert_array_equal(res.left_take, olt)
    np.testing.assert_array_equal(res.right_take, ort)
