"""Column.take_from_parts: the winners taken straight from per-file columns.

The primitive must give exactly Column.concat(parts).take(take): values,
validity, dtype, backing and, for code-backed columns, pool and codes. The
read path uses it for the value columns of the keys-only pipeline
(MergeFileSplitRead._gather_winners), where every engine and the serial
map must give the batch that read_kv's whole-batch merge gives.
"""

import numpy as np
import pyarrow as pa
import pytest

import paimon_tpu as pt
from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.core.read import MergeFileSplitRead
from paimon_tpu.data.batch import Column, PartsTake
from paimon_tpu.metrics import registry

LENGTHS = (5, 0, 7, 3)  # the second part is empty
MIXED = np.array([14, 0, 3, 5, 5, 12, 1, 9], dtype=np.int32)  # neither monotone nor free of repeats


def _ints(rng, n, part):
    return Column(rng.integers(-(1 << 40), 1 << 40, n))


def _doubles(rng, n, part):
    v = rng.normal(size=n)
    v[::3] = np.nan
    return Column(v)


def _nullable(rng, n, part):
    return Column(rng.integers(0, 100, n), (rng.random(n) < 0.5) if part % 2 else None)


def _strings(rng, n, part):
    return Column(arrow=pa.array([None if rng.random() < 0.3 else f"s{rng.integers(9)}" for _ in range(n)], type=pa.string()))


def _codes(rng, n, part):
    pool = np.array(sorted({f"p{part}-{rng.integers(6)}" for _ in range(4)} | {"shared"}), dtype=object)
    validity = (rng.random(n) < 0.7) if n and part != 3 else None
    return Column.from_codes(pool, rng.integers(0, len(pool), n).astype(np.uint32), validity)


def _mixed_backing(rng, n, part):
    if part % 2:
        return Column(np.array([f"v{i}" for i in range(n)], dtype=object))
    return Column(arrow=pa.array([f"a{i}" for i in range(n)], type=pa.string()))


_POOL = np.array(["a", "b", "c"], dtype=object)

CASES = {
    "bigint": (_ints, LENGTHS, MIXED),
    "double-with-nan": (_doubles, LENGTHS, MIXED),
    "nullable-parts-among-non-null": (_nullable, LENGTHS, MIXED),
    "nullable-but-no-null-wins": (lambda rng, n, part: Column(np.arange(n), np.arange(n) > 0 if part == 0 else None),
                                  LENGTHS, np.array([1, 2, 6, 14], dtype=np.int32)),
    "code-backed-different-pools": (_codes, LENGTHS, MIXED),
    "code-backed-one-pool": (lambda rng, n, part: Column.from_codes(_POOL, rng.integers(0, 3, n).astype(np.uint32)), LENGTHS, MIXED),
    "arrow-strings": (_strings, LENGTHS, MIXED),
    "mixed-backing-falls-back": (_mixed_backing, LENGTHS, MIXED),
    "mixed-dtypes-fall-back": (lambda rng, n, part: Column(np.arange(n, dtype=np.int32 if part else np.int64)), LENGTHS, MIXED),
    "pool-past-the-limit-falls-back": (_codes, LENGTHS, MIXED),
    "single-part": (_ints, (9,), np.array([8, 0, 4, 4], dtype=np.int64)),
    "single-part-strings": (_strings, (9,), np.array([8, 0, 4, 4], dtype=np.int64)),
    "no-winners": (_ints, LENGTHS, np.empty(0, dtype=np.int32)),
    "no-winners-strings": (_strings, LENGTHS, np.empty(0, dtype=np.int32)),
    "no-winners-codes": (_codes, LENGTHS, np.empty(0, dtype=np.int32)),
    "winners-from-one-part": (_nullable, LENGTHS, np.array([5, 6, 8, 11], dtype=np.int32)),
    "ascending-across-parts": (_doubles, LENGTHS, np.array([0, 4, 5, 11, 12, 14], dtype=np.int32)),
    "more-winners-than-a-stretch": (_nullable, (700, 0, 900, 400), None),
}

CONCATENATED = {"arrow-strings", "single-part-strings", "no-winners-strings", "mixed-backing-falls-back", "mixed-dtypes-fall-back",
                "pool-past-the-limit-falls-back"}


def assert_same_column(got: Column, want: Column):
    assert len(got) == len(want)
    assert (got._values is None, got.arrow is None, got.dict_cache is None) == \
           (want._values is None, want.arrow is None, want.dict_cache is None)
    if want.validity is None:
        assert got.validity is None
    else:
        assert got.validity.dtype == np.bool_ and np.array_equal(got.validity, want.validity)
    if want._values is not None:
        assert got._values.dtype == want._values.dtype
        assert np.array_equal(got._values, want._values, equal_nan=want._values.dtype.kind == "f")
    if want.arrow is not None:
        assert type(got.arrow) is type(want.arrow) and got.arrow.type == want.arrow.type
        assert got.arrow.equals(want.arrow)
    if want.dict_cache is not None:
        for g, w in zip(got.dict_cache, want.dict_cache):
            assert g.dtype == w.dtype and np.array_equal(g, w)


@pytest.mark.parametrize("case", CASES)
def test_take_from_parts_equals_the_take_of_the_concatenation(case, monkeypatch):
    make, lengths, take = CASES[case]
    if take is None:  # several stretches of the plan, in a shuffled order
        monkeypatch.setattr(PartsTake, "CHUNK", 256)
        take = np.random.default_rng(3).permutation(sum(lengths))[:1500].astype(np.int32)
    if case.startswith("pool-past-the-limit"):
        monkeypatch.setenv("PAIMON_TPU_DICT_POOL_LIMIT", "3")

    def fresh():
        rng = np.random.default_rng(7)
        return [make(rng, n, part) for part, n in enumerate(lengths)]

    def expanded():
        return registry.snapshot().get("dict", {}).get("fallback_expanded", 0)

    parts = fresh()
    offsets = np.concatenate([[0], np.cumsum(lengths)])
    counters = (registry.snapshot().get("read"), expanded())
    want = Column.concat(fresh()).take(take)  # of parts of its own: a code-backed part that expands keeps its values
    counted = expanded() - counters[1]
    plan = PartsTake(offsets, take)
    got, from_parts = Column.take_from_parts(parts, plan)
    assert registry.snapshot().get("read") == counters[0]  # the read path's counters are the read path's to keep
    assert expanded() - counters[1] == 2 * counted  # dict{fallback_expanded} counts what the concatenation's take counts
    assert (counted > 0) == case.startswith("pool-past-the-limit")
    assert_same_column(got, want)
    # fresh arrays: nothing of the output is a view of a part
    for p in parts:
        for mine, theirs in ((got._values, p._values), (got.validity, p.validity)):
            assert mine is None or theirs is None or not np.shares_memory(mine, theirs)
    # only a column that nobody concatenated counts as from parts: not one that took the concatenation
    # after all, and not an arrow-backed one (pyarrow's take joins variable-width chunks inside)
    assert from_parts is (case not in CONCATENATED)
    for part, positions, rows in plan.picks:
        assert positions.dtype == rows.dtype == np.intp and (np.diff(positions) > 0).all()
        assert np.array_equal(take[positions], rows + offsets[part])
    assert sum(len(p[1]) for p in plan.picks) == len(take)


def test_a_take_beyond_the_parts_is_an_index_error():
    for bad in ([15], [-1], [0, 3, 99]):
        with pytest.raises(IndexError):
            PartsTake([0, 5, 5, 12, 15], np.array(bad, dtype=np.int32))


# ---- through the read path -------------------------------------------------

RUNS, KEYS = 4, 4_000
PROJECTION = ["s", "id", "d", "c"]


@pytest.fixture(scope="module")
def four_runs(tmp_path_factory):
    """Four overlapping runs with nulls in every value column and deletes in
    the later runs, left uncompacted so that the read has to merge them."""
    catalog = FileSystemCatalog(str(tmp_path_factory.mktemp("parts") / "warehouse"), commit_user="parts")
    table = catalog.create_table(
        "db.t",
        pt.RowType.of(("id", pt.BIGINT(False)), ("c", pt.BIGINT()), ("d", pt.DOUBLE()), ("s", pt.STRING()), ("e", pt.BIGINT())),
        primary_keys=["id"], options={"bucket": "1", "write-only": "true"})
    rng = np.random.default_rng(5)
    for r in range(RUNS):
        ids = np.sort(rng.choice(KEYS, 1_500, replace=False)).astype(np.int64)
        null = lambda values: [None if rng.random() < 0.2 else v for v in values]  # noqa: E731
        wb = table.new_batch_write_builder()
        w = wb.new_write()
        w.write({"id": ids, "c": null((ids * 10 + r).tolist()), "d": null((ids / 7 + r).tolist()),
                 "s": null([f"s{i % 11}-{r}" for i in ids]), "e": null(ids.tolist())},
                kinds=["-D" if r and rng.random() < 0.15 else "+I" for _ in ids])
        wb.new_commit().commit(w.prepare_commit())
    return table


def _split_read(table, parallelism=None):
    store = table.store
    files = store.restore_files((), 0)
    assert len(files) == RUNS
    return MergeFileSplitRead(store.reader_factory((), 0), store.merge_executor(), store.key_names, parallelism), files


def _rows(batch):
    return {n: (batch.column(n).to_pylist(), np.asarray(batch.column(n).values).dtype) for n in batch.schema.field_names}


@pytest.mark.parametrize("engine,parallelism", [("device", None), ("device", 1), ("numpy", None), ("numpy", 1)])
def test_the_read_path_gives_the_batch_of_the_whole_batch_merge(four_runs, engine, parallelism, monkeypatch):
    from paimon_tpu.options import SortEngine
    from paimon_tpu.utils.cache import clear_all

    if engine == "numpy":  # conftest pins the device kernels; without the pin a CPU backend adapts to numpy
        monkeypatch.delenv("PAIMON_TPU_FORCE_DEVICE_ENGINE")
    read, files = _split_read(four_runs, parallelism)
    assert (read.merge.effective_sort_engine() == SortEngine.NUMPY) == (engine == "numpy")
    clear_all()
    before = dict(registry.snapshot().get("read", {}))
    got = read.read_split(files, projection=PROJECTION)
    counted = {k: v - before.get(k, 0) for k, v in registry.snapshot()["read"].items()}
    want = read.read_kv(files, drop_delete=True).data.select(PROJECTION)
    assert got.schema.field_names == PROJECTION and 0 < got.num_rows < KEYS
    assert _rows(got) == _rows(want)
    assert any(v is None for v in got.column("s").to_pylist())
    # 7 arrays gathered, before the deletes are dropped; from parts the three numeric value columns (c, d, e):
    # not the arrow-backed STRING column, which pyarrow joins inside its take, nor the key column, seq and kind
    winners = counted["rows_gathered"] // 7
    assert winners > got.num_rows and counted["rows_gathered"] == 7 * winners
    assert counted["rows_gathered_from_parts"] == 3 * winners
