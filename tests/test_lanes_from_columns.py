"""Packed key lanes straight from the key columns (ops.lanes.compress_key_columns).

Where every key column of a merge is a plain fixed-width integer column, the
sort operands and their LanePlan are made from the columns in one pass; the
(n, K) lane matrix of data.keys.encode_key_lanes and its re-packing by
ops.lanes.compress_key_lanes are then never built. That matrix path stays, for
every other key, and is the reference here: the column entry has to return the
same plan and the same array, bit for bit, or decline.
"""

import numpy as np
import pytest

from paimon_tpu.core.kv import KVBatch
from paimon_tpu.core.mergefn import MergeExecutor
from paimon_tpu.data import keys as K
from paimon_tpu.data.batch import Column, ColumnBatch
from paimon_tpu.metrics import lanes_metrics, registry
from paimon_tpu.ops import lanes as L
from paimon_tpu.options import CoreOptions, MergeEngine
from paimon_tpu.types import (
    BIGINT, BOOLEAN, DATE, DOUBLE, FLOAT, INT, SMALLINT, STRING, TIMESTAMP, TINYINT, RowKind, RowType,
)

from test_tracing import _named, traced  # noqa: E402

N = 5_000


@pytest.fixture(autouse=True)
def _layer_as_the_options_say(monkeypatch):
    # scripts/verify.sh forces the layer off for a whole pass; these tests
    # say themselves where it is off
    monkeypatch.delenv("PAIMON_TPU_LANE_COMPRESSION", raising=False)


def _batch(*columns):
    """columns: (name, type, values or Column)."""
    schema = RowType.of(*[(name, typ) for name, typ, _ in columns])
    return ColumnBatch(schema, {name: v if isinstance(v, Column) else Column(v) for name, _, v in columns})


def _ints(rng, lo, hi, dtype, n=N):
    return rng.integers(lo, hi, n, dtype=np.int64).astype(dtype)


def _one(name, typ, values):
    return _batch((name, typ, values)), [name]


def _two(a, b, c=None):
    """A composite key of an INT (or SMALLINT) column `a`, a BIGINT `b` and, where given, a DATE `c`."""
    cols = [("a", SMALLINT(False) if a.dtype == np.int16 else INT(False), a), ("b", BIGINT(False), b)]
    if c is not None:
        cols.append(("c", DATE(False), c))
    return _batch(*cols), [name for name, _, _ in cols]


_I64 = np.iinfo(np.int64)
_I32 = np.iinfo(np.int32)

# name -> (rng) -> (batch, key names): every key a plain integer column
INTEGER_KEYS = {
    "bigint_dense": lambda rng: _one("id", BIGINT(False), rng.permutation(3 * np.arange(N, dtype=np.int64) + 1)),
    "bigint_several_high_words": lambda rng: _one("id", BIGINT(False), _ints(rng, 0, 1 << 35, np.int64)),
    "bigint_negative": lambda rng: _one("id", BIGINT(False), _ints(rng, -(1 << 40), 1 << 40, np.int64)),
    "bigint_negative_one_high_word": lambda rng: _one("id", BIGINT(False), _ints(rng, -5_000, -10, np.int64)),
    "bigint_whole_range": lambda rng: _one("id", BIGINT(False), np.concatenate(
        [_ints(rng, -(1 << 62), 1 << 62, np.int64, N - 2), np.array([_I64.min, _I64.max])])),
    "int": lambda rng: _one("k", INT(False), _ints(rng, -70_000, 70_000, np.int32)),
    "int_whole_range": lambda rng: _one("k", INT(False), np.array([_I32.min, -1, 0, _I32.max], dtype=np.int32)),
    "smallint": lambda rng: _one("k", SMALLINT(False), _ints(rng, -300, 300, np.int16)),
    "tinyint": lambda rng: _one("k", TINYINT(False), _ints(rng, -128, 128, np.int8)),
    "date": lambda rng: _one("d", DATE(False), _ints(rng, 18_000, 20_000, np.int32)),
    "timestamp": lambda rng: _one(
        "t", TIMESTAMP(nullable=False), _ints(rng, 1_600_000_000_000_000, 1_700_000_000_000_000, np.int64)),
    "composite_fuses_into_one_operand": lambda rng: _two(
        _ints(rng, -5, 11, np.int32), _ints(rng, 20, 1 << 20, np.int64)),
    "composite_does_not_fuse": lambda rng: _two(_ints(rng, -5, 11, np.int32), _ints(rng, 0, 1 << 31, np.int64)),
    "composite_of_three": lambda rng: _two(
        _ints(rng, 0, 100, np.int16), _ints(rng, 0, 1 << 34, np.int64), _ints(rng, 0, 50, np.int32)),
    "composite_with_a_constant_column": lambda rng: _two(np.full(N, 9, np.int32), _ints(rng, 0, 1 << 20, np.int64)),
    "all_constant": lambda rng: _one("id", BIGINT(False), np.full(N, -7, np.int64)),
    "rows_0": lambda rng: _one("id", BIGINT(False), np.arange(0, dtype=np.int64)),
    "rows_1": lambda rng: _one("id", BIGINT(False), np.arange(1, dtype=np.int64)),
    "rows_2": lambda rng: _one("id", BIGINT(False), np.array([5, -5], dtype=np.int64)),
}


@pytest.mark.parametrize("enable_ovc", [True, False], ids=["device", "host"])
@pytest.mark.parametrize("case", sorted(INTEGER_KEYS))
def test_the_column_entry_is_the_matrix_path_bit_for_bit(rng, case, enable_ovc):
    batch, key_names = INTEGER_KEYS[case](rng)
    want, want_plan = L.compress_key_lanes(K.encode_key_lanes(batch, key_names), True, enable_ovc=enable_ovc)
    columns = K.integer_key_columns(batch, key_names)
    assert columns is not None
    got, plan = L.compress_key_columns(columns, True, enable_ovc)
    assert plan == want_plan
    assert got.dtype == np.uint32 and got.shape == want.shape and got.flags.c_contiguous
    assert np.array_equal(got, want)


def test_the_cases_cover_fused_split_and_zero_width_plans(rng):
    plans = {}
    for case, make in INTEGER_KEYS.items():
        batch, key_names = make(rng)
        plans[case] = L.compress_key_columns(K.integer_key_columns(batch, key_names), True)[1]
    assert plans["bigint_dense"] == L.LanePlan(2, (1,), (0,), (14,), ((0,),))  # the cells' key: the low word alone
    assert plans["bigint_several_high_words"].groups == ((0,), (1,))
    fused, apart = plans["composite_fuses_into_one_operand"], plans["composite_does_not_fuse"]
    assert fused.groups == ((0, 1),) and any(fused.los)
    assert apart.groups == ((0,), (1,)) and not any(apart.los)
    # a fused pair, then two operands alone that keep their shift since something fused
    assert plans["composite_of_three"].groups == ((0, 1), (2,), (3,)) and all(plans["composite_of_three"].los)
    assert plans["all_constant"].lanes_out == plans["rows_1"].lanes_out == 0


def _strings(rng):
    return np.array([f"k{i:04d}" for i in rng.integers(0, 900, N)], dtype=object)


def _code_backed_key(rng):
    pool = np.arange(0, 3_000, 3, dtype=np.int64)
    return _one("id", BIGINT(False), Column.from_codes(pool, rng.integers(0, len(pool), N).astype(np.uint32)))


NOT_INTEGER_KEYS = {
    "string": lambda rng: _one("s", STRING(False), _strings(rng)),
    "string_and_bigint": lambda rng: (
        _batch(("s", STRING(False), _strings(rng)), ("id", BIGINT(False), _ints(rng, 0, 99, np.int64))), ["s", "id"]),
    "double": lambda rng: _one("x", DOUBLE(False), rng.normal(size=N)),
    "float": lambda rng: _one("x", FLOAT(False), rng.normal(size=N).astype(np.float32)),
    "boolean": lambda rng: _one("b", BOOLEAN(False), rng.random(N) < 0.5),
    "code_backed": _code_backed_key,
    "with_a_validity_mask": lambda rng: _one("id", BIGINT(), Column(_ints(rng, 0, 99, np.int64), rng.random(N) < 0.9)),
    "int_type_over_an_int64_array": lambda rng: _one("k", INT(False), _ints(rng, 0, 99, np.int64)),
}


@pytest.mark.parametrize("case", sorted(NOT_INTEGER_KEYS))
def test_other_keys_are_left_to_the_matrix_path(rng, case):
    batch, key_names = NOT_INTEGER_KEYS[case](rng)
    assert K.integer_key_columns(batch, key_names) is None


def _ovc_eligible(rng):
    columns = [(name, INT(False), _ints(rng, 0, 1 << 20, np.int32)) for name in ("a", "b")]
    return _batch(*columns), ["a", "b"]


def test_a_plan_with_an_ovc_lane_declines_and_counts_nothing(rng):
    batch, key_names = _ovc_eligible(rng)
    assert L.plan_lanes(K.encode_key_lanes(batch, key_names)).use_ovc
    registry.reset()
    assert L.compress_key_columns(K.integer_key_columns(batch, key_names), True) is None
    assert lanes_metrics().counter("plans").count == 0
    # the host engine carries no code lane, so there the same key packs
    assert L.compress_key_columns(K.integer_key_columns(batch, key_names), True, enable_ovc=False)[1].lanes_out == 2


@pytest.mark.parametrize("how", ["option", "environment"])
def test_the_layer_off_declines(rng, monkeypatch, how):
    batch, key_names = INTEGER_KEYS["bigint_dense"](rng)
    if how == "environment":
        monkeypatch.setenv("PAIMON_TPU_LANE_COMPRESSION", "0")
    assert L.compress_key_columns(K.integer_key_columns(batch, key_names), how == "environment") is None


# ---------------------------------------------------------------------------
# MergeExecutor: the same winners whichever way the lanes were made
# ---------------------------------------------------------------------------

def _kv(data):
    n = data.num_rows
    return KVBatch(data, np.arange(n, dtype=np.int64), np.full(n, int(RowKind.INSERT), np.uint8))


def _four_runs(rng, key="bigint"):
    """Four key-sorted runs over one key space, concatenated in run order
    (ascending sequence numbers), so a key's winner is its last run's row."""
    runs = [np.sort(rng.choice(9_000, 2_500, replace=False)) for _ in range(4)]
    ids = np.concatenate(runs).astype(np.int64)
    offsets = np.concatenate([[0], np.cumsum([len(r) for r in runs])]).tolist()
    if key == "bigint":
        cols = [("id", BIGINT(False), 3 * ids + 1)]
    elif key == "composite":  # three varying lanes, two operands: a fused with b's high word, then b's low word
        cols = [("a", INT(False), (ids // 100).astype(np.int32)), ("b", BIGINT(False), (ids % 100) << 26)]
    elif key == "ovc":  # two operands of 20 bits or fewer each: the device plan carries a code lane
        a, b = (ids // 3).astype(np.int32) << 10, (ids % 3).astype(np.int32) << 18
        cols = [("a", INT(False), a), ("b", INT(False), b)]
    else:
        cols = [("id", STRING(False), np.array([f"k{i:05d}" for i in ids], dtype=object))]
    key_names = [name for name, _, _ in cols]
    data = _batch(*cols)
    kv = _kv(data)
    last = {}
    for row, i in enumerate(ids.tolist()):
        last[i] = row
    return kv, key_names, offsets, np.array([last[i] for i in sorted(last)], dtype=np.int64)


def _executor(kv, key_names, **options):
    return MergeExecutor(kv.data.schema, key_names, MergeEngine.DEDUPLICATE, CoreOptions(options))


ENGINES = {"numpy": {"sort-engine": "numpy"}, "xla": {}}  # conftest pins the device engine on the CPU


@pytest.mark.parametrize("dispatch", ["tiled", "tiled_into_two", "single_with_seq_lanes"])
@pytest.mark.parametrize("key", ["bigint", "composite", "ovc"])
@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_dedup_select_picks_the_same_winners_on_both_paths(rng, monkeypatch, engine, key, dispatch):
    kv, key_names, offsets, want = _four_runs(rng, key)
    options = dict(ENGINES[engine])
    if dispatch == "tiled_into_two":
        options["merge.read-batch-rows"] = "6000"
    ex = _executor(kv, key_names, **options)

    def select():
        if dispatch == "single_with_seq_lanes":
            return ex.dedup_resolve(ex.dedup_select_async(kv, seq_ascending=False))
        return ex.dedup_resolve(ex.dedup_select_async(kv, seq_ascending=True, run_offsets=offsets))

    registry.reset()
    fused = select()
    counted = lanes_metrics().counter("plans_from_columns").count
    monkeypatch.setattr(K, "integer_key_columns", lambda batch, key_names: None)
    registry.reset()
    matrix = select()
    assert lanes_metrics().counter("plans_from_columns").count == 0 and lanes_metrics().counter("plans").count == 1
    assert np.array_equal(fused, matrix) and np.array_equal(np.asarray(fused, dtype=np.int64), want)
    # the device plan of the "ovc" key carries a code lane: such a key keeps the matrix path
    assert counted == (0 if key == "ovc" and engine == "xla" else 1)


@pytest.mark.parametrize("shuffled", [True, False], ids=["shuffled", "sorted_unique"])
@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_merge_is_the_same_on_both_paths(rng, monkeypatch, engine, shuffled):
    kv, key_names, _, want = _four_runs(rng)
    if shuffled:
        order = rng.permutation(kv.num_rows)
        kv = kv.take(order)  # seq travels with its row, so explicit sequence lanes decide
    else:
        kv = kv.take(want)  # key-sorted and unique: no sort at all
    ex = _executor(kv, key_names, **ENGINES[engine])
    registry.reset()
    fused = ex.merge(kv, seq_ascending=False)
    assert lanes_metrics().counter("plans_from_columns").count == lanes_metrics().counter("plans").count == 1
    monkeypatch.setattr(K, "integer_key_columns", lambda batch, key_names: None)
    matrix = ex.merge(kv, seq_ascending=False)
    assert fused.data.to_pylist() == matrix.data.to_pylist()
    assert np.array_equal(fused.seq, matrix.seq) and np.array_equal(fused.seq, want)


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_a_stream_of_tiles_takes_packed_lanes(rng, monkeypatch, engine):
    # above ops.merge._STREAM_TILE_ROWS a writer's merge runs as key-range tiles of one shape
    n = 150_000
    ids = rng.integers(0, 90_000, n).astype(np.int64) * 7 - 100_000
    kv = _kv(_batch(("id", BIGINT(False), ids)))
    ex = _executor(kv, ["id"], **ENGINES[engine])
    registry.reset()
    fused = ex.merge(kv, seq_ascending=True)
    assert lanes_metrics().counter("plans_from_columns").count == lanes_metrics().counter("plans").count == 1
    monkeypatch.setattr(K, "integer_key_columns", lambda batch, key_names: None)
    matrix = ex.merge(kv, seq_ascending=True)
    assert np.array_equal(fused.seq, matrix.seq) and fused.num_rows == len(np.unique(ids))
    assert np.array_equal(fused.data.column("id").values, np.unique(ids))


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_the_counter_says_how_often_the_columns_were_enough(rng, engine):
    registry.reset()
    kv, key_names, offsets, _ = _four_runs(rng)
    ex = _executor(kv, key_names, **ENGINES[engine])
    for _ in range(3):
        ex.dedup_resolve(ex.dedup_select_async(kv, seq_ascending=True, run_offsets=offsets))
    g = lanes_metrics()
    assert g.counter("plans_from_columns").count == g.counter("plans").count == 3
    assert g.counter("lanes_in").count == 6 and g.counter("lanes_out").count == 3  # counted as the matrix path counts
    registry.reset()
    kv, key_names, offsets, _ = _four_runs(rng, "string")
    ex = _executor(kv, key_names, **ENGINES[engine])
    ex.dedup_resolve(ex.dedup_select_async(kv, seq_ascending=True, run_offsets=offsets))
    assert lanes_metrics().counter("plans").count == 1 and lanes_metrics().counter("plans_from_columns").count == 0


@pytest.mark.parametrize("key,packed", [("bigint", True), ("composite", True), ("string", False), ("ovc", False)])
def test_the_fused_pass_runs_under_lanes_encode_and_nothing_is_packed_again(rng, tmp_path, key, packed):
    kv, key_names, offsets, _ = _four_runs(rng, key)
    ex = _executor(kv, key_names)
    with traced(tmp_path) as events:
        ex.dedup_resolve(ex.dedup_select_async(kv, seq_ascending=True, run_offsets=offsets))
    encodes = _named(events, "lanes.encode")
    if packed:
        (encode,) = encodes
        assert encode[4]["packed"] == 1 and encode[4]["rows"] == kv.num_rows
        assert encode[4]["lanes"] == (1 if key == "bigint" else 2)  # operands out
        assert not _named(events, "lanes.compress")
    else:
        assert encodes and all("packed" not in e[4] for e in encodes)
        (compress,) = _named(events, "lanes.compress")
        assert compress[4]["parent"] == "merge.dispatch"
