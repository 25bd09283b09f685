"""Field aggregators for the aggregation merge engine, as segment reductions.

Capability parity with the reference aggregator family
(/root/reference/paimon-core/.../mergetree/compact/aggregate/ — 18
FieldAggregator subclasses: sum, product, count, max, min, bool_and, bool_or,
first_value, first_non_null_value, last_value, last_non_null_value, listagg,
collect, merge_map, nested_update, primary-key, ignore-retract wrapper).

Numeric/bool/min/max/count/sum run on device as segment reductions over
the MergePlan's sorted order; first/last pick per-segment row indices (gather
stays exact for any type, including strings); listagg/collect run host-side
per segment (variable-length outputs cannot live on device anyway).

How a segment is reduced on the device. The sorted order makes each segment
a run of consecutive rows, so integer sums and counts, the validity bit and
the picks read a scan over the sorted rows at each segment's bounds: an
integer sum is the inclusive prefix sum at the segment's end less the one at
the previous segment's end (exact in wrapping arithmetic: the bits of a
scatter-add, modulo 2**width); the validity bit a prefix count; a last
(first) pick a running max (reverse running min) of the candidates' sorted
positions read at the end (start). Floating sums keep `segment_sum`, since a
prefix difference in floating point cancels, and min/max keep
`segment_max`/`segment_min` for their values: those scatter into `seg_id`.

Retract rows (-U/-D): sum and count subtract; ignore-retract drops them for
a field; everything else raises — the same contract as the reference
(FieldAggregator.retract throws UnsupportedOperationException).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
from jax import lax

from ..data.batch import Column
from ..metrics import dict_metrics, span
from ..types import RowKind
from .merge import MergePlan, _jit, pad_to

__all__ = [
    "AggregateSpec",
    "aggregate_merge",
    "AGGREGATORS",
    "segment_reduce",
    "segment_reduce_np",
]

AGGREGATORS = (
    "sum",
    "product",
    "count",
    "max",
    "min",
    "bool_and",
    "bool_or",
    "first_value",
    "first_non_null_value",
    "last_value",
    "last_non_null_value",
    "listagg",
    "collect",
    "merge_map",
    "nested_update",
    "primary-key",
)

_RETRACTABLE = {"sum", "count"}


@dataclass(frozen=True)
class AggregateSpec:
    function: str
    ignore_retract: bool = False
    listagg_delimiter: str = ","
    collect_distinct: bool = False
    nested_key: tuple[str, ...] = ()  # nested_update: ARRAY<ROW> upsert key


def _scans(dtype) -> bool:
    """Whether a sum of `dtype` contributions takes the prefix difference:
    an integer's wrapping arithmetic makes it exact (the bits a scatter-add
    gives, modulo 2**width); a floating sum would cancel, so it keeps
    `segment_sum`."""
    return np.issubdtype(dtype, np.integer)


def _segment_sum(contrib, ends, seg_id=None):
    """In-kernel: per segment, the sum of `contrib` (sorted order), segment
    i spanning the sorted rows (ends[i-1], ends[i]]. Integers: the inclusive
    prefix sum read at each end less the one read at the end before (no
    scatter, which the chip emulates for s64). Floats: `segment_sum` over
    `seg_id`."""
    if _scans(contrib.dtype):
        at_end = jnp.cumsum(contrib, dtype=contrib.dtype)[ends]
        return at_end - jnp.concatenate([jnp.zeros((1,), at_end.dtype), at_end[:-1]])
    return jax.ops.segment_sum(contrib, seg_id, num_segments=ends.shape[0])


def _any_valid(ok, ends):
    """In-kernel: per segment, whether any of its rows is ok (a prefix count)."""
    return _segment_sum(ok.astype(jnp.int32), ends) > 0


def _pick(ok, ends, last: bool):
    """In-kernel: per segment, the sorted position of its last (first) ok
    row, -1 where none: a running max (a reverse running min) of the ok
    rows' positions read at the segment's end (start)."""
    m = ok.shape[0]
    pos = jnp.arange(m, dtype=jnp.int32)
    before = jnp.concatenate([jnp.full((1,), -1, ends.dtype), ends[:-1]])
    if last:
        best = lax.cummax(jnp.where(ok, pos, -1), axis=0)[ends]
        return jnp.where(best > before, best, -1)
    best = lax.cummin(jnp.where(ok, pos, m), axis=0, reverse=True)[before + 1]
    return jnp.where(best <= ends, best, -1)


def _segment_extreme(v, ok, seg_id, num_segments: int, is_max: bool):
    """In-kernel: per segment, the max (min) of `v` over its ok rows, the
    dtype's least (greatest) value where none."""
    info = jnp.finfo(v.dtype) if jnp.issubdtype(v.dtype, jnp.floating) else jnp.iinfo(v.dtype)
    masked = jnp.where(ok, v, info.min if is_max else info.max)
    reduce = jax.ops.segment_max if is_max else jax.ops.segment_min
    return reduce(masked, seg_id, num_segments=num_segments)


@functools.lru_cache(maxsize=None)
def _sum_fn():
    @_jit("agg_sum")
    def f(perm, ends, seg_id, values, valid, sign):
        # seg_id: None for integer values, which never scatter
        ok = valid[perm]
        contrib = jnp.where(ok, values[perm] * sign[perm], jnp.zeros((), values.dtype))
        return _segment_sum(contrib, ends, seg_id), _any_valid(ok, ends)

    return f


@functools.lru_cache(maxsize=None)
def _minmax_fn(is_max: bool):
    @_jit("agg_minmax")
    def f(perm, ends, seg_id, values, valid):
        ok = valid[perm]
        return _segment_extreme(values[perm], ok, seg_id, ends.shape[0], is_max), _any_valid(ok, ends)

    return f


@functools.lru_cache(maxsize=None)
def _pick_fn(last: bool):
    @_jit("agg_pick")
    def f(perm, ends, candidate):
        # candidate: (m,) bool in INPUT coords — rows eligible to be picked
        # (validity and/or retract-exclusion already folded in by the caller)
        best = _pick(candidate[perm], ends, last)
        return jnp.where(best >= 0, perm[jnp.clip(best, 0, perm.shape[0] - 1)], -1)

    return f


def pick_rows(plan: MergePlan, candidate: np.ndarray, last: bool = True) -> np.ndarray:
    """Per segment of `plan`, the input row of its last (first) candidate
    row, -1 where it has none."""
    src = _pick_fn(last)(plan.perm, plan.segment_ends, pad_to(candidate, plan.m, False))
    return np.asarray(src)[: plan.num_segments]


def _product_host(plan: MergePlan, values: np.ndarray, eff_valid: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Exact segmented product via np.multiply.reduceat over the sorted order
    (cumprod-ratio tricks on device lose exactness at zeros/int division)."""
    order = plan.perm[plan.valid_sorted]
    v = values.take(order)
    ok = eff_valid.take(order)
    contrib = np.where(ok, v, np.ones((), values.dtype))
    bounds = np.flatnonzero(plan.seg_start[plan.valid_sorted])
    total = np.multiply.reduceat(contrib, bounds)
    any_valid = np.maximum.reduceat(ok.astype(np.int8), bounds) > 0
    return total, any_valid


def _signs(row_kind: np.ndarray, spec: AggregateSpec, dtype) -> tuple[np.ndarray, np.ndarray]:
    """(sign, include) per input row given retract semantics."""
    retract = np.isin(row_kind, (int(RowKind.UPDATE_BEFORE), int(RowKind.DELETE)))
    if spec.ignore_retract:
        return np.ones(len(row_kind), dtype=dtype), ~retract
    if spec.function in _RETRACTABLE:
        sign = np.where(retract, -1, 1).astype(dtype)
        return sign, np.ones(len(row_kind), dtype=np.bool_)
    if retract.any():
        raise ValueError(
            f"aggregate function {spec.function!r} cannot retract; "
            f"use ignore-retract or an input without -U/-D rows"
        )
    return np.ones(len(row_kind), dtype=dtype), np.ones(len(row_kind), dtype=np.bool_)


def _sign_dtype(column: Column):
    """The dtype of `_signs`' arrays for a column, read without building its
    values: the column's own, int64 for strings and bytes."""
    return np.int64 if column.dtype == np.dtype(object) else column.dtype


def aggregate_merge(
    plan: MergePlan,
    column: Column,
    spec: AggregateSpec,
    row_kind: np.ndarray,
) -> Column:
    """Aggregate one value column over the plan's segments. Returns a Column
    of length plan.num_segments (key order)."""
    m, k = plan.m, plan.num_segments
    valid = column.valid_mask()
    fn = spec.function

    if fn in ("listagg", "collect", "merge_map", "nested_update"):
        return _host_aggregate(plan, column.values, valid, spec, row_kind)

    if fn == "primary-key":
        # always the latest arrival, null or not, retract rows included
        # (reference FieldPrimaryKeyAgg: agg/retract both return inputField)
        return _gather_column(column, pick_rows(plan, np.ones(len(column), np.bool_)))

    sign, include = _signs(row_kind, spec, _sign_dtype(column))
    eff_valid = valid & include

    if fn in _PICK_FNS:
        # *_value picks may land on a null row; *_non_null_value requires
        # validity. Both must respect the retract include-mask. The picks
        # never read the values: a string column stays in arrow or codes
        candidate = eff_valid if "non_null" in fn else include
        return _gather_column(column, pick_rows(plan, candidate, fn.startswith("last")))

    values = column.values
    if values.dtype == np.dtype(object):
        raise ValueError(f"aggregate {fn!r} unsupported for string/bytes columns")

    perm, ends = plan.perm, plan.segment_ends
    if fn in ("bool_and", "bool_or"):
        v8 = values.astype(np.int8)
        agg, any_valid = _minmax_fn(fn == "bool_or")(
            perm, ends, plan.seg_id, pad_to(v8, m, 0), pad_to(eff_valid, m, False)
        )
        out = np.asarray(agg)[:k].astype(np.bool_)
        av = np.asarray(any_valid)[:k]
        return Column(out, av if not av.all() else None)

    if (
        fn in ("max", "min", "sum")
        and values.dtype == np.float64
        and _f64_on_device_unsupported()
    ):
        out, av = _host_reduce(plan, values, eff_valid, fn, sign if fn == "sum" else None)
        return Column(out.astype(values.dtype, copy=False), av if not av.all() else None)
    if fn in ("max", "min"):
        agg, any_valid = _minmax_fn(fn == "max")(
            perm, ends, plan.seg_id, pad_to(values, m, 0), pad_to(eff_valid, m, False)
        )
    elif fn == "sum":
        agg, any_valid = _sum_fn()(
            perm,
            ends,
            None if _scans(values.dtype) else plan.seg_id,
            pad_to(values, m, 0),
            pad_to(eff_valid, m, False),
            pad_to(sign, m, 1),
        )
    elif fn == "count":
        ones = np.ones(len(values), dtype=np.int64)
        agg, _ = _sum_fn()(
            perm, ends, None, pad_to(ones, m, 0), pad_to(eff_valid, m, False), pad_to(sign.astype(np.int64), m, 1)
        )
        return Column(np.asarray(agg)[:k])  # count of nothing is 0, not null
    elif fn == "product":
        out, av = _product_host(plan, values, eff_valid)
        return Column(out.astype(values.dtype, copy=False), av if not av.all() else None)
    else:
        raise ValueError(f"unknown aggregate function {fn!r}; known: {AGGREGATORS}")

    out = np.asarray(agg)[:k].astype(values.dtype, copy=False)
    av = np.asarray(any_valid)[:k]
    return Column(out, av if not av.all() else None)


_DEVICE_FNS = ("sum", "count", "max", "min", "bool_and", "bool_or")
_PICK_FNS = ("first_value", "first_non_null_value", "last_value", "last_non_null_value")


def _f64_on_device_unsupported() -> bool:
    """TPUs have no native f64 ALUs: float64 reductions must stay host-exact
    there (CPU runs them on device under jax x64)."""
    import jax

    return jax.default_backend() == "tpu"


def field_on(spec: AggregateSpec, column: Column) -> str:
    """Where aggregate_merge reduces a field: "pick" (a first/last or
    primary-key pick on the device, then the row taken on the host), "host"
    (listagg, collect, merge_map, nested_update, product, and f64 sum/max/min
    where the backend has no exact f64) or "device" (a segment reduction)."""
    fn = spec.function
    if fn in _PICK_FNS or fn == "primary-key":
        return "pick"
    if fn in ("listagg", "collect", "merge_map", "nested_update", "product"):
        return "host"
    if fn in ("max", "min", "sum") and column.dtype == np.float64 and _f64_on_device_unsupported():
        return "host"
    return "device"


def takes_scan(spec: AggregateSpec, column: Column) -> bool:
    """Whether a field's reduction reads scans at the segments' bounds, on
    either route: the picks and primary-key, count, and integer sums (not
    floating sums, min, max, bool_and/bool_or or the host's functions)."""
    fn = spec.function
    return fn in _PICK_FNS or fn in ("primary-key", "count") or (fn == "sum" and _scans(column.dtype))


def fused_routable(specs: list[AggregateSpec], columns: list[Column]) -> bool:
    """True when every column can run inside the single fused kernel:
    numeric reductions and first/last picks. product stays host-exact,
    listagg/collect build variable-length host outputs, and f64 reductions
    leave the device path on TPU backends (no native f64)."""
    f64_off_device = _f64_on_device_unsupported()
    for spec, col in zip(specs, columns):
        if spec.function in _PICK_FNS:
            continue
        if spec.function not in _DEVICE_FNS:
            return False
        if col.dtype == np.dtype(object):
            return False
        if f64_off_device and col.dtype == np.float64 and spec.function != "count":
            return False
    return True


def _host_reduce(plan: MergePlan, values: np.ndarray, eff_valid: np.ndarray, fn: str, sign=None):
    """Exact segmented f64 sum/max/min on host over the sorted order (the
    f64-on-TPU route: the chip emulates f64 and its sums are not exact).
    Sums accumulate row by row in (key, sequence) order — bincount adds
    sequentially, where add.reduceat folds a + (b + c) — the reference's
    FieldSumAgg order and the order the CPU device path produces."""
    order = plan.perm[plan.valid_sorted]
    v = values.take(order)
    ok = eff_valid.take(order)
    bounds = np.flatnonzero(plan.seg_start[plan.valid_sorted])
    if fn == "sum":
        s = sign.take(order) if sign is not None else np.ones_like(v)
        contrib = np.where(ok, v * s, np.zeros((), v.dtype))
        total = np.bincount(plan.seg_id[plan.valid_sorted], weights=contrib, minlength=len(bounds))
    elif fn == "max":
        contrib = np.where(ok, v, np.full((), -np.inf, v.dtype))
        total = np.maximum.reduceat(contrib, bounds)
    else:  # min
        contrib = np.where(ok, v, np.full((), np.inf, v.dtype))
        total = np.minimum.reduceat(contrib, bounds)
    any_valid = np.maximum.reduceat(ok.astype(np.int8), bounds) > 0
    return total, any_valid


@functools.lru_cache(maxsize=None)
def _fused_aggregate_fn(num_key: int, num_seq: int, col_fns: tuple[str, ...]):
    """Sort + every column's segment reduction in ONE kernel (the aggregation
    analog of the fused dedup kernel): uploads lanes + value columns once,
    downloads only the (C, k) results — no plan arrays, no per-column
    round trips. col_fns entries: sum|count|max|min|bool_and|bool_or|
    pick_first|pick_last."""

    from .merge import pack_selected, sorted_segments

    @_jit("fused_aggregate")
    def f(key_lanes, seq_lanes, pad_flag, values, valids, signs):
        m = pad_flag.shape[0]
        pad_sorted, perm, _, keep_last, seg_id = sorted_segments(num_key, num_seq, key_lanes, seq_lanes, pad_flag)
        # the segments' last sorted rows ride the sort that packs the winners
        packed, ends, count = pack_selected(keep_last & (pad_sorted == 0), perm, jnp.arange(m, dtype=jnp.int32))
        outs = []
        anyv = []
        for i, fn in enumerate(col_fns):
            ok = valids[i][perm]
            if fn.startswith("pick_"):
                best = _pick(ok, ends, fn == "pick_last")
                outs.append(jnp.where(best >= 0, perm[jnp.clip(best, 0, m - 1)], -1))
                anyv.append(best >= 0)
                continue
            v = values[i][perm]
            if fn in ("sum", "count"):
                s = signs[i][perm].astype(v.dtype)
                outs.append(_segment_sum(jnp.where(ok, v * s, jnp.zeros((), v.dtype)), ends, seg_id))
            else:
                outs.append(_segment_extreme(v, ok, seg_id, m, fn in ("max", "bool_or")))
            anyv.append(_any_valid(ok, ends))
        return tuple(outs), tuple(anyv), packed, count

    return f


def fused_aggregate(
    key_lanes: np.ndarray,  # (n, K) uint32
    seq_lanes: np.ndarray | None,
    columns: list[Column],
    specs: list[AggregateSpec],
    row_kind: np.ndarray,
    compress: bool | None = None,
    names: list[str] | None = None,
) -> tuple[list[Column], np.ndarray]:
    """Single-call aggregation merge over every value column. Returns
    (aggregated columns in key order, last_take winning-row indices). Key
    lanes run through the compression seam (ops/lanes.py) — identical
    segmentation, fewer sort operands. Each column's result is taken from
    the kernel's outputs under an `agg.field` span (`names` name them)."""
    from .merge import prepare_lanes_planned

    klp, slp, pad, n, k, s, m, _plan = prepare_lanes_planned(key_lanes, seq_lanes, compress=compress)
    col_fns = []
    values = []
    valids = []
    signs = []
    for spec, col in zip(specs, columns):
        fn = spec.function
        sign, include = _signs(row_kind, spec, _sign_dtype(col))
        valid = col.valid_mask()
        if fn in _PICK_FNS:
            candidate = (valid & include) if "non_null" in fn else include
            col_fns.append("pick_last" if fn.startswith("last") else "pick_first")
            values.append(np.zeros(m, np.int8))  # unused by picks
            valids.append(pad_to(candidate, m, False))
            signs.append(np.ones(m, np.int8))
        elif fn == "count":
            col_fns.append("count")
            values.append(pad_to(np.ones(n, np.int64), m, 0))
            valids.append(pad_to(valid & include, m, False))
            signs.append(pad_to(sign.astype(np.int8), m, 1))
        elif fn in ("bool_and", "bool_or"):
            col_fns.append(fn)
            values.append(pad_to(col.values.astype(np.int8), m, 0))
            valids.append(pad_to(valid & include, m, False))
            signs.append(np.ones(m, np.int8))
        else:
            col_fns.append(fn)
            values.append(pad_to(col.values, m, 0))
            valids.append(pad_to(valid & include, m, False))
            signs.append(pad_to(sign.astype(np.int8), m, 1))
    outs, anyv, packed, count = _fused_aggregate_fn(k, s, tuple(col_fns))(
        klp, slp, pad, tuple(values), tuple(valids), tuple(signs)
    )
    kk = int(count)
    result: list[Column] = []
    for i, (spec, col, fn, o, av) in enumerate(zip(specs, columns, col_fns, outs, anyv)):
        with span("agg.field", field=names[i] if names else str(i), fn=spec.function,
                  on="pick" if fn.startswith("pick_") else "device"):
            if fn.startswith("pick_"):
                result.append(_gather_column(col, np.asarray(o[:kk])))
            elif fn == "count":
                result.append(Column(np.asarray(o[:kk])))  # count of nothing is 0
            else:
                vals = np.asarray(o[:kk]).astype(col.dtype, copy=False)
                valid = np.asarray(av[:kk])
                if fn in ("bool_and", "bool_or"):
                    vals = vals.astype(np.bool_)
                result.append(Column(vals, valid if not valid.all() else None))
    return result, np.asarray(packed[:kk])


def _gather_column(column: Column, src: np.ndarray) -> Column:
    """The rows `src` names of `column`, null where `src` is -1, in the
    domain the column is backed by: codes, arrow or numpy."""
    ok = src >= 0
    safe = np.clip(src, 0, max(len(column) - 1, 0))
    validity = ok & column.valid_mask().take(safe)
    if column.is_code_backed:
        # compressed domain: gather the codes, keep the pool — partial-update
        # and aggregation winners never materialize the strings
        pool, codes = column.dict_cache
        return Column.from_codes(pool, codes.take(safe), validity)
    if column.arrow is not None:
        # arrow's take, the -1 slots null indices: no string object is built
        # (dict{rows_taken_in_arrow}), whether or not `.values` was filled
        taken = pc.take(column.arrow, pa.array(src, mask=None if ok.all() else ~ok))
        if isinstance(taken, pa.ChunkedArray):
            taken = taken.chunk(0) if taken.num_chunks == 1 else taken.combine_chunks()
        if taken.null_count != len(src) - int(validity.sum()):
            # a validity that masks rows the arrow holds (as a LEFT join's
            # unmatched side has it): the nulls follow the validity
            taken = pc.if_else(pa.array(validity), taken, pa.scalar(None, taken.type))
        dict_metrics().counter("rows_taken_in_arrow").inc(len(src))
        return Column(validity=validity, arrow=taken)
    vals = column.values.take(safe)
    if column.values.dtype != np.dtype(object):
        vals = np.where(validity, vals, np.zeros((), column.values.dtype))
    return Column(vals, validity if not validity.all() else None)


# ---- GROUP BY segment-reduce (ISSUE 16) ---------------------------------
#
# The SQL group-by primitive: group keys arrive as uint32 lanes (dictionary
# codes or narrowed fixed-width values), value columns reduce per segment in
# ONE fused sort+reduce kernel through the same sorted_segments seam the
# merge path uses — lane compression included. Unlike
# aggregate_merge there is no sequence dimension and no retract handling:
# every row contributes, and the caller additionally gets each group's
# minimum input position so first-appearance output order (and distributed
# combines keyed on global row position) stay exact.

_SEGMENT_REDUCE_FNS = ("sum", "count", "min", "max")


@functools.lru_cache(maxsize=None)
def _segment_reduce_fn(num_lanes: int, col_fns: tuple[str, ...]):
    from .merge import pack_selected, sorted_segments

    @jax.jit
    def f(key_lanes, pad_flag, pos, values, valids):
        m = pad_flag.shape[0]
        pad_sorted, perm, seg_start, _keep_last, seg_id = sorted_segments(num_lanes, 0, key_lanes, [], pad_flag)
        valid_rows = pad_sorted == 0
        packed, starts, count = pack_selected(seg_start & valid_rows, perm, jnp.arange(m, dtype=jnp.int32))
        # the groups tile the valid rows: each ends where the next starts,
        # the last at the last valid row
        ends = jnp.where(
            jnp.arange(m) < count - 1,
            jnp.concatenate([starts[1:], starts[:1]]) - 1,
            valid_rows.sum().astype(jnp.int32) - 1,
        )
        outs = []
        anyv = []
        for i, fn in enumerate(col_fns):
            v = values[i][perm]
            ok = valids[i][perm]
            if fn in ("sum", "count"):
                outs.append(_segment_sum(jnp.where(ok, v, jnp.zeros((), v.dtype)), ends, seg_id))
            else:
                outs.append(_segment_extreme(v, ok, seg_id, m, fn == "max"))
            anyv.append(_any_valid(ok, ends))
        first_pos = jax.ops.segment_min(pos[perm], seg_id, num_segments=m)
        return tuple(outs), tuple(anyv), first_pos, packed, count

    return f


def segment_reduce(
    key_lanes: np.ndarray,  # (n, K) uint32
    columns: list[tuple[np.ndarray, np.ndarray | None]],  # (values, valid) per column
    fns: tuple[str, ...],  # sum|count|min|max per column
    pos: np.ndarray | None = None,  # (n,) int64 global row positions
    engine: str = "xla",
    compress: bool | None = None,
):
    """Segment-reduce `columns` over groups keyed by `key_lanes` rows.

    Returns ``(rep, outs, anyv, first_pos)`` with groups in KEY order:
    ``rep[g]`` is the input index of one representative row of group g,
    ``outs[i][g]`` the reduction of column i over group g (masked rows
    contribute identity), ``anyv[i][g]`` whether any row of group g was
    valid for column i, and ``first_pos[g]`` the minimum `pos` over the
    group (first-appearance ordering / distributed combine key).

    engine: "numpy" routes to the exact host twin, "xla" to the device
    kernel; there too f64 columns leave the
    device on TPU backends (no native f64, same rule as aggregate_merge);
    fully constant key lanes (k == 0 after compression) take the twin too —
    a single group is not worth a device round trip."""
    from .merge import prepare_lanes_planned

    n = int(key_lanes.shape[0])
    if pos is None:
        pos = np.arange(n, dtype=np.int64)
    vals = [(v, np.ones(n, np.bool_) if ok is None else ok) for v, ok in columns]
    if (
        engine == "numpy"
        or n == 0
        or (
            _f64_on_device_unsupported()
            and any(v.dtype == np.float64 for v, _ in vals)
        )
    ):
        return segment_reduce_np(key_lanes, vals, fns, pos)
    klp, slp, pad, _n, k, s, m, _plan = prepare_lanes_planned(key_lanes, None, compress=compress)
    if k == 0:
        return segment_reduce_np(key_lanes, vals, fns, pos)
    from ..metrics import sql_metrics

    sql_metrics().counter("rows_reduced_device").inc(n)
    big = np.iinfo(np.int64).max
    outs, anyv, first_pos, packed, count = _segment_reduce_fn(k, tuple(fns))(
        klp,
        pad,
        jnp.asarray(pad_to(pos.astype(np.int64, copy=False), m, big)),
        tuple(jnp.asarray(pad_to(v, m, 0)) for v, _ in vals),
        tuple(jnp.asarray(pad_to(ok, m, False)) for _, ok in vals),
    )
    g = int(count)
    return (
        np.asarray(packed[:g]),
        [np.asarray(o[:g]).astype(v.dtype, copy=False) for o, (v, _) in zip(outs, vals)],
        [np.asarray(a[:g]) for a in anyv],
        np.asarray(first_pos[:g]),
    )


def segment_reduce_np(
    key_lanes: np.ndarray,
    columns: list[tuple[np.ndarray, np.ndarray]],
    fns: tuple[str, ...],
    pos: np.ndarray,
):
    """Exact numpy twin of segment_reduce: lexsort + reduceat, identical
    output contract (groups in key order)."""
    n = int(key_lanes.shape[0])
    if n == 0:
        return (
            np.zeros(0, np.int64),
            [np.zeros(0, v.dtype) for v, _ in columns],
            [np.zeros(0, np.bool_) for _ in columns],
            np.zeros(0, np.int64),
        )
    kk = key_lanes.shape[1]
    order = np.lexsort(tuple(key_lanes[:, i] for i in range(kk - 1, -1, -1)))
    sk = key_lanes[order]
    neq = (sk[1:] != sk[:-1]).any(axis=1) if n > 1 else np.zeros(0, np.bool_)
    starts = np.flatnonzero(np.concatenate([[True], neq]))
    outs = []
    anyv = []
    for (v, ok), fn in zip(columns, fns):
        vs = v[order]
        oks = ok[order]
        if fn in ("sum", "count"):
            contrib = np.where(oks, vs, np.zeros((), v.dtype))
            outs.append(np.add.reduceat(contrib, starts))
        elif fn == "max":
            fill = np.finfo(v.dtype).min if v.dtype.kind == "f" else np.iinfo(v.dtype).min
            outs.append(np.maximum.reduceat(np.where(oks, vs, fill), starts))
        else:
            fill = np.finfo(v.dtype).max if v.dtype.kind == "f" else np.iinfo(v.dtype).max
            outs.append(np.minimum.reduceat(np.where(oks, vs, fill), starts))
        anyv.append(np.maximum.reduceat(oks.astype(np.int8), starts) > 0)
    first_pos = np.minimum.reduceat(pos[order], starts)
    return order[starts], outs, anyv, first_pos


def _host_aggregate(plan: MergePlan, values, valid, spec: AggregateSpec, row_kind) -> Column:
    """listagg / collect / merge_map / nested_update: variable-length or
    structured outputs, built per segment on host from the sorted order
    (still no comparator loops — slicing only)."""
    k = plan.num_segments
    order = plan.perm[plan.valid_sorted]
    v_sorted = values.take(order)
    ok_sorted = valid.take(order)
    retract = np.isin(row_kind, (int(RowKind.UPDATE_BEFORE), int(RowKind.DELETE))).take(order)
    if spec.ignore_retract:
        ok_sorted = ok_sorted & ~retract
        retract = np.zeros_like(retract)
    elif retract.any() and spec.function == "listagg":
        raise ValueError("listagg cannot retract; configure ignore-retract")
    bounds = np.flatnonzero(plan.seg_start[plan.valid_sorted])
    out = np.empty(k, dtype=object)
    validity = np.zeros(k, dtype=np.bool_)
    for s in range(k):
        lo = bounds[s]
        hi = bounds[s + 1] if s + 1 < k else len(order)
        if spec.function == "merge_map":
            out[s], validity[s] = _merge_map_segment(v_sorted, ok_sorted, retract, lo, hi)
            continue
        if spec.function == "nested_update":
            out[s], validity[s] = _nested_update_segment(
                v_sorted, ok_sorted, retract, lo, hi, spec.nested_key
            )
            continue
        if spec.function == "listagg":
            vals = [v_sorted[i] for i in range(lo, hi) if ok_sorted[i]]
            if vals:
                out[s] = spec.listagg_delimiter.join(str(x) for x in vals)
                validity[s] = True
        else:  # collect
            vals = []
            for i in range(lo, hi):
                if not ok_sorted[i]:
                    continue
                x = v_sorted[i]
                # an input may be a raw scalar OR an already-collected list
                # (a stored row re-merged with new arrivals): flatten lists so
                # re-aggregation is associative (reference FieldCollectAgg
                # concatenates array inputs)
                items = list(x) if isinstance(x, (list, tuple)) else [x]
                if retract[i]:
                    # reference FieldCollectAgg removes matching elements
                    for item in items:
                        if item in vals:
                            vals.remove(item)
                else:
                    vals.extend(items)
            if spec.collect_distinct:
                seen = []
                for x in vals:
                    if x not in seen:
                        seen.append(x)
                vals = seen
            out[s] = vals
            validity[s] = True
    return Column(out, validity if not validity.all() else None)


def _merge_map_segment(v_sorted, ok_sorted, retract, lo, hi):
    """Dict union in (key, seq) order; null inputs keep the accumulator;
    retract rows remove their keys (reference FieldMergeMapAgg)."""
    acc = None
    for i in range(lo, hi):
        if not ok_sorted[i]:
            continue
        m = v_sorted[i]
        if retract[i]:
            if acc:
                for key in dict(m):
                    acc.pop(key, None)
            continue
        if acc is None:
            acc = dict(m)
        else:
            acc.update(m)
    return acc, acc is not None


def _row_key(row, nested_key):
    if isinstance(row, dict):
        return tuple(row.get(f) for f in nested_key)
    return tuple(row)  # full-row identity when no key configured


def _nested_update_segment(v_sorted, ok_sorted, retract, lo, hi, nested_key):
    """ARRAY<ROW> upsert: concat in order; with a nested key, later rows
    replace earlier rows sharing the key; retract rows remove matching
    elements (reference FieldNestedUpdateAgg)."""
    acc = None
    for i in range(lo, hi):
        if not ok_sorted[i]:
            continue
        rows = v_sorted[i] or []
        if retract[i]:
            if acc:
                if nested_key:
                    dead = {_row_key(r, nested_key) for r in rows}
                    acc = [r for r in acc if _row_key(r, nested_key) not in dead]
                else:
                    for r in rows:
                        if r in acc:
                            acc.remove(r)
            continue
        if acc is None:
            acc = list(rows)
        else:
            acc.extend(rows)
    if acc is not None and nested_key:
        by_key = {}
        for r in acc:
            by_key[_row_key(r, nested_key)] = r  # last wins
        acc = list(by_key.values())
    return acc, acc is not None
