"""The picks of the aggregation and partial-update merges take their winners
in the domain a column is backed by (`ops/aggregates.py:_gather_column`):
codes stay codes, an arrow-backed STRING column is taken by
`pyarrow.compute.take` with the `-1` slots as null indices, and no Python
string is built on the way (`dict{rows_taken_in_arrow}` counts the rows taken
so, `dict{arrow_expanded}` the rows `Column.values` turned into objects).
Each backing is held to the object path it replaces, cell for cell; the
merges that call it (`aggregate_merge`, `fused_aggregate`) to a numpy
reference, with the input column's values never built.
"""

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from paimon_tpu.data.batch import Column
from paimon_tpu.metrics import dict_metrics, registry
from paimon_tpu.ops.aggregates import AggregateSpec, _gather_column, aggregate_merge, fused_aggregate
from paimon_tpu.ops.merge import merge_plan
from paimon_tpu.types import RowKind

N = 400
PICKS = ("first_value", "first_non_null_value", "last_value", "last_non_null_value")


def _strings(seed, n=N, null_share=0.3):
    rng = np.random.default_rng(seed)
    valid = rng.random(n) >= null_share
    values = np.array([f"w{int(x)}" if ok else None for x, ok in zip(rng.integers(0, 50, n), valid)], dtype=object)
    return values, valid


def _counted(name):
    return dict_metrics().counter(name).count


def _backed(kind, values, valid):
    """A column of `values` (None where not `valid`) backed as `kind` says."""
    arrow = pa.array(values.tolist(), type=pa.string())
    if kind == "numpy":
        return Column(values.copy(), valid)
    if kind == "arrow":
        return Column(validity=valid, arrow=arrow)
    if kind == "arrow-chunked":
        return Column(validity=valid, arrow=pa.chunked_array([arrow.slice(0, 150), arrow.slice(150)]))
    if kind == "arrow-concat":
        # three parts, the middle one all null and null-typed (cast in by the concat)
        head, tail = slice(0, 100), slice(140, N)
        assert not valid[100:140].any()
        parts = [Column(validity=valid[head], arrow=arrow[head]),
                 Column(validity=valid[100:140], arrow=pa.nulls(40)),
                 Column(validity=valid[tail], arrow=arrow[tail])]
        col = Column.concat(parts)
        assert isinstance(col.arrow, pa.Array) and col.arrow.type == pa.string()
        return col
    if kind == "arrow-null-typed":
        # every row null, as arrow types a column it never saw a value of
        return Column(validity=np.zeros(len(values), np.bool_), arrow=pa.nulls(len(values)))
    if kind == "arrow-filled":
        col = Column(validity=valid, arrow=arrow)
        col.values  # noqa: B018 — `.values` read before the take
        return col
    if kind == "arrow-masked":
        # the arrow holds every row; the validity masks some (a LEFT join's unmatched side)
        full = pa.array([v if v is not None else "masked" for v in values.tolist()], type=pa.string())
        return Column(validity=valid, arrow=full)
    if kind == "codes":
        pool = np.array(sorted({v for v in values if v is not None}), dtype=object)
        codes = np.where(valid, np.searchsorted(pool, np.where(valid, values, pool[0])), 0).astype(np.uint32)
        return Column.from_codes(pool, codes, valid)
    raise AssertionError(kind)


def _object_take(values, valid, src):
    """The object path `_gather_column` had for every backing: python values
    of the taken rows, None where `src` is -1 or the row is null."""
    ok = src >= 0
    safe = np.clip(src, 0, len(values) - 1)
    taken_valid = ok & valid[safe]
    return [v if t else None for v, t in zip(values[safe].tolist(), taken_valid.tolist())], taken_valid


BACKINGS = ["numpy", "arrow", "arrow-chunked", "arrow-concat", "arrow-null-typed", "arrow-filled", "arrow-masked", "codes"]


@pytest.mark.parametrize("kind", BACKINGS)
def test_a_take_of_winners_is_the_object_path_in_every_backing(kind):
    values, valid = _strings(7)
    valid[100:140] = False
    values[100:140] = None
    rng = np.random.default_rng(8)
    src = rng.integers(-1, N, 300).astype(np.int32)
    src[:5] = -1
    src[5:10] = np.flatnonzero(~valid)[:5]  # null source rows
    if kind == "arrow-null-typed":
        values, valid = np.full(N, None, dtype=object), np.zeros(N, np.bool_)
    column = _backed(kind, values, valid)
    want, want_valid = _object_take(values, valid, src)
    registry.reset()
    got = _gather_column(column, src)
    in_arrow = kind.startswith("arrow")
    assert _counted("rows_taken_in_arrow") == (len(src) if in_arrow else 0)
    assert _counted("arrow_expanded") == 0
    if in_arrow:
        assert isinstance(got.arrow, pa.Array) and got._values is None
        assert np.array_equal(np.asarray(pc.is_valid(got.arrow)), want_valid)  # arrow's nulls are the validity
        assert kind == "arrow-filled" or column._values is None
    else:
        assert got.is_code_backed == (kind == "codes")
    assert np.array_equal(got.valid_mask(), want_valid)
    assert got.to_pylist() == want


def _case(seed, n=N, k=90, retract=False):
    rng = np.random.default_rng(seed)
    keys = rng.permutation(np.concatenate([np.arange(k), rng.integers(0, k, n - k)])).astype(np.uint32)
    kind = np.full(n, int(RowKind.INSERT), np.uint8)
    if retract:
        kind[rng.random(n) < 0.2] = int(RowKind.UPDATE_BEFORE)
    values, valid = _strings(seed + 1, n)
    return keys, kind, values, valid


def _expected(keys, values, valid, fn, candidate=None):
    """Per key in key order, the first/last `candidate` row in input order
    (of the non-null ones for *_non_null_value; primary-key: the last row)."""
    out = []
    for key in np.unique(keys):
        rows = np.flatnonzero((keys == key) & (True if candidate is None else candidate))
        pool = rows[valid[rows]] if "non_null" in fn else rows
        row = None if not len(pool) else pool[0] if fn.startswith("first") else pool[-1]
        out.append(None if row is None else values[row])
    return out


@pytest.mark.parametrize("fn", [*PICKS, "primary-key"])
def test_planned_picks_of_an_arrow_column_never_build_its_strings(fn):
    keys, kind, values, valid = _case(11)
    column = _backed("arrow", values, valid)
    plan = merge_plan(keys.reshape(-1, 1))
    registry.reset()
    got = aggregate_merge(plan, column, AggregateSpec(fn), kind)
    assert column._values is None and got._values is None
    assert _counted("rows_taken_in_arrow") == plan.num_segments and _counted("arrow_expanded") == 0
    assert got.to_pylist() == _expected(keys, values, valid, fn)


def test_fused_picks_of_an_arrow_column_never_build_its_strings():
    keys, kind, values, valid = _case(13)
    column = _backed("arrow", values, valid)
    registry.reset()
    got, last_take = fused_aggregate(keys.reshape(-1, 1), None, [column] * len(PICKS),
                                     [AggregateSpec(fn) for fn in PICKS], kind)
    k = len(last_take)
    assert column._values is None and _counted("arrow_expanded") == 0
    assert _counted("rows_taken_in_arrow") == len(PICKS) * k
    for fn, out in zip(PICKS, got):
        assert out._values is None and out.to_pylist() == _expected(keys, values, valid, fn), fn


@pytest.mark.parametrize("route", ["planned", "fused"])
@pytest.mark.parametrize("fn", PICKS)
def test_a_pick_over_retract_rows_still_raises(route, fn):
    keys, kind, values, valid = _case(17, retract=True)
    column = _backed("arrow", values, valid)
    with pytest.raises(ValueError, match="cannot retract"):
        if route == "planned":
            aggregate_merge(merge_plan(keys.reshape(-1, 1)), column, AggregateSpec(fn), kind)
        else:
            fused_aggregate(keys.reshape(-1, 1), None, [column], [AggregateSpec(fn)], kind)
    # ignore-retract drops the -U/-D rows from the candidates instead
    got = aggregate_merge(merge_plan(keys.reshape(-1, 1)), column, AggregateSpec(fn, ignore_retract=True), kind)
    assert column._values is None
    assert got.to_pylist() == _expected(keys, values, valid, fn, candidate=kind == int(RowKind.INSERT))
