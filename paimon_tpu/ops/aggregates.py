"""Field aggregators for the aggregation merge engine, as segment reductions.

Capability parity with the reference aggregator family
(/root/reference/paimon-core/.../mergetree/compact/aggregate/ — 18
FieldAggregator subclasses: sum, product, count, max, min, bool_and, bool_or,
first_value, first_non_null_value, last_value, last_non_null_value, listagg,
collect, merge_map, nested_update, primary-key, ignore-retract wrapper).

Numeric/bool/min/max/count/sum run on device as jax segment reductions over
the MergePlan's sorted order; first/last pick per-segment row indices (gather
stays exact for any type, including strings); listagg/collect run host-side
per segment (variable-length outputs cannot live on device anyway).

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

from ..data.batch import Column
from ..types import RowKind
from .merge import MergePlan, pad_to

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


@functools.lru_cache(maxsize=None)
def _sum_fn():
    @jax.jit
    def f(perm, seg_id, values, valid, sign):
        m = perm.shape[0]
        v = values[perm]
        ok = valid[perm]
        s = sign[perm]
        contrib = jnp.where(ok, v * s, jnp.zeros((), values.dtype))
        total = jax.ops.segment_sum(contrib, seg_id, num_segments=m)
        any_valid = jax.ops.segment_max(ok.astype(jnp.int32), seg_id, num_segments=m) > 0
        return total, any_valid

    return f


@functools.lru_cache(maxsize=None)
def _minmax_fn(is_max: bool):
    @jax.jit
    def f(perm, seg_id, values, valid):
        m = perm.shape[0]
        v = values[perm]
        ok = valid[perm]
        if is_max:
            fill = jnp.finfo(values.dtype).min if jnp.issubdtype(values.dtype, jnp.floating) else jnp.iinfo(values.dtype).min
            masked = jnp.where(ok, v, fill)
            agg = jax.ops.segment_max(masked, seg_id, num_segments=m)
        else:
            fill = jnp.finfo(values.dtype).max if jnp.issubdtype(values.dtype, jnp.floating) else jnp.iinfo(values.dtype).max
            masked = jnp.where(ok, v, fill)
            agg = jax.ops.segment_min(masked, seg_id, num_segments=m)
        any_valid = jax.ops.segment_max(ok.astype(jnp.int32), seg_id, num_segments=m) > 0
        return agg, any_valid

    return f


@functools.lru_cache(maxsize=None)
def _pick_fn(last: bool):
    @jax.jit
    def f(perm, seg_id, candidate):
        # candidate: (m,) bool in INPUT coords — rows eligible to be picked
        # (validity and/or retract-exclusion already folded in by the caller)
        m = perm.shape[0]
        pos = jnp.arange(m, dtype=jnp.int32)
        ok = candidate[perm]
        if last:
            cand = jnp.where(ok, pos, -1)
            best = jax.ops.segment_max(cand, seg_id, num_segments=m)
        else:
            cand = jnp.where(ok, pos, m)
            best = jax.ops.segment_min(cand, seg_id, num_segments=m)
            best = jnp.where(best == m, -1, best)
        src = jnp.where(best >= 0, perm[jnp.clip(best, 0, m - 1)], -1)
        return src

    return f


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


def aggregate_merge(
    plan: MergePlan,
    column: Column,
    spec: AggregateSpec,
    row_kind: np.ndarray,
) -> Column:
    """Aggregate one value column over the plan's segments. Returns a Column
    of length plan.num_segments (key order)."""
    m, k = plan.m, plan.num_segments
    values = column.values
    valid = column.valid_mask()
    fn = spec.function

    if fn in ("listagg", "collect", "merge_map", "nested_update"):
        return _host_aggregate(plan, values, valid, spec, row_kind)

    if fn == "primary-key":
        # always the latest arrival, null or not, retract rows included
        # (reference FieldPrimaryKeyAgg: agg/retract both return inputField)
        src_idx = _pick_fn(True)(
            jnp.asarray(plan.perm),
            jnp.asarray(plan.seg_id),
            jnp.asarray(pad_to(np.ones(len(values), np.bool_), m, False)),
        )
        return _gather_column(column, np.asarray(src_idx)[:k])

    sign, include = _signs(row_kind, spec, values.dtype if values.dtype != np.dtype(object) else np.int64)
    eff_valid = valid & include

    perm = jnp.asarray(plan.perm)
    seg_id = jnp.asarray(plan.seg_id)

    if fn in ("first_value", "first_non_null_value", "last_value", "last_non_null_value"):
        # *_value picks may land on a null row; *_non_null_value requires
        # validity. Both must respect the retract include-mask.
        candidate = eff_valid if "non_null" in fn else include
        src = _pick_fn(fn.startswith("last"))(perm, seg_id, jnp.asarray(pad_to(candidate, m, False)))
        src = np.asarray(src)[:k]
        return _gather_column(column, src)

    if values.dtype == np.dtype(object):
        raise ValueError(f"aggregate {fn!r} unsupported for string/bytes columns")

    if fn in ("bool_and", "bool_or"):
        v8 = values.astype(np.int8)
        agg, any_valid = _minmax_fn(fn == "bool_or")(
            perm, seg_id, jnp.asarray(pad_to(v8, m, 0)), jnp.asarray(pad_to(eff_valid, m, False))
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
            perm, seg_id, jnp.asarray(pad_to(values, m, 0)), jnp.asarray(pad_to(eff_valid, m, False))
        )
    elif fn == "sum":
        agg, any_valid = _sum_fn()(
            perm,
            seg_id,
            jnp.asarray(pad_to(values, m, 0)),
            jnp.asarray(pad_to(eff_valid, m, False)),
            jnp.asarray(pad_to(sign, m, 1)),
        )
    elif fn == "count":
        ones = np.ones(len(values), dtype=np.int64)
        agg, any_valid = _sum_fn()(
            perm,
            seg_id,
            jnp.asarray(pad_to(ones, m, 0)),
            jnp.asarray(pad_to(eff_valid, m, False)),
            jnp.asarray(pad_to(sign.astype(np.int64), m, 1)),
        )
        out = np.asarray(agg)[:k]
        return Column(out)  # count of nothing is 0, not null
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
        if col.values.dtype == np.dtype(object):
            return False
        if f64_off_device and col.values.dtype == np.float64 and spec.function != "count":
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
def _fused_aggregate_fn(num_key: int, num_seq: int, col_fns: tuple[str, ...], engine: str = "xla"):
    """Sort + every column's segment reduction in ONE kernel (the aggregation
    analog of the fused dedup kernel): uploads lanes + value columns once,
    downloads only the (C, k) results — no plan arrays, no per-column
    round trips. col_fns entries: sum|count|max|min|bool_and|bool_or|
    pick_first|pick_last."""

    from .merge import pack_selected, sorted_segments

    @jax.jit
    def f(key_lanes, seq_lanes, pad_flag, values, valids, signs):
        m = pad_flag.shape[0]
        pad_sorted, perm, _, keep_last, seg_id = sorted_segments(
            num_key, num_seq, key_lanes, seq_lanes, pad_flag, engine=engine
        )
        pos = jnp.arange(m, dtype=jnp.int32)
        outs = []
        anyv = []
        for i, fn in enumerate(col_fns):
            ok = valids[i][perm]
            if fn.startswith("pick_"):
                last = fn == "pick_last"
                if last:
                    cand = jnp.where(ok, pos, -1)
                    best = jax.ops.segment_max(cand, seg_id, num_segments=m)
                else:
                    cand = jnp.where(ok, pos, m)
                    best = jax.ops.segment_min(cand, seg_id, num_segments=m)
                    best = jnp.where(best == m, -1, best)
                outs.append(jnp.where(best >= 0, perm[jnp.clip(best, 0, m - 1)], -1))
                anyv.append(best >= 0)
                continue
            v = values[i][perm]
            if fn in ("sum", "count"):
                s = signs[i][perm].astype(v.dtype)
                contrib = jnp.where(ok, v * s, jnp.zeros((), v.dtype))
                agg = jax.ops.segment_sum(contrib, seg_id, num_segments=m)
            else:
                is_max = fn in ("max", "bool_or")
                if jnp.issubdtype(v.dtype, jnp.floating):
                    fill = jnp.finfo(v.dtype).min if is_max else jnp.finfo(v.dtype).max
                else:
                    fill = jnp.iinfo(v.dtype).min if is_max else jnp.iinfo(v.dtype).max
                masked = jnp.where(ok, v, fill)
                agg = (
                    jax.ops.segment_max(masked, seg_id, num_segments=m)
                    if is_max
                    else jax.ops.segment_min(masked, seg_id, num_segments=m)
                )
            outs.append(agg)
            anyv.append(jax.ops.segment_max(ok.astype(jnp.int32), seg_id, num_segments=m) > 0)
        packed, count = pack_selected(keep_last & (pad_sorted == 0), perm)
        return tuple(outs), tuple(anyv), packed, count

    return f


def fused_aggregate(
    key_lanes: np.ndarray,  # (n, K) uint32
    seq_lanes: np.ndarray | None,
    columns: list[Column],
    specs: list[AggregateSpec],
    row_kind: np.ndarray,
    compress: bool | None = None,
    engine: str = "xla",
) -> tuple[list[Column], np.ndarray]:
    """Single-call aggregation merge over every value column. Returns
    (aggregated columns in key order, last_take winning-row indices). Key
    lanes run through the compression seam (ops/lanes.py) — identical
    segmentation, fewer sort operands."""
    from .merge import prepare_lanes_planned

    klp, slp, pad, n, k, s, m, _plan = prepare_lanes_planned(key_lanes, seq_lanes, compress=compress)
    col_fns = []
    values = []
    valids = []
    signs = []
    for spec, col in zip(specs, columns):
        fn = spec.function
        sign, include = _signs(
            row_kind, spec, col.values.dtype if col.values.dtype != np.dtype(object) else np.int64
        )
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
    if engine == "pallas":
        from .pallas_kernels import note_dispatch

        note_dispatch(m)
    outs, anyv, packed, count = _fused_aggregate_fn(k, s, tuple(col_fns), engine)(
        klp, slp, pad, tuple(values), tuple(valids), tuple(signs)
    )
    kk = int(count)
    result: list[Column] = []
    for spec, col, fn, o, av in zip(specs, columns, col_fns, outs, anyv):
        if fn.startswith("pick_"):
            result.append(_gather_column(col, np.asarray(o[:kk])))
        elif fn == "count":
            result.append(Column(np.asarray(o[:kk])))  # count of nothing is 0
        else:
            vals = np.asarray(o[:kk]).astype(col.values.dtype, copy=False)
            valid = np.asarray(av[:kk])
            if fn in ("bool_and", "bool_or"):
                vals = vals.astype(np.bool_)
            result.append(Column(vals, valid if not valid.all() else None))
    return result, np.asarray(packed[:kk])


def _gather_column(column: Column, src: np.ndarray) -> Column:
    ok = src >= 0
    safe = np.clip(src, 0, max(len(column) - 1, 0))
    validity = ok & column.valid_mask().take(safe)
    if column.is_code_backed:
        # compressed domain: gather the codes, keep the pool — partial-update
        # and aggregation winners never materialize the strings
        pool, codes = column.dict_cache
        return Column.from_codes(pool, codes.take(safe), validity)
    vals = column.values.take(safe)
    if column.values.dtype != np.dtype(object):
        vals = np.where(validity, vals, np.zeros((), column.values.dtype))
    return Column(vals, validity if not validity.all() else None)


# ---- GROUP BY segment-reduce (ISSUE 16) ---------------------------------
#
# The SQL group-by primitive: group keys arrive as uint32 lanes (dictionary
# codes or narrowed fixed-width values), value columns reduce per segment in
# ONE fused sort+reduce kernel through the same sorted_segments seam the
# merge path uses — pallas/xla/lane-compression all inherit it. Unlike
# aggregate_merge there is no sequence dimension and no retract handling:
# every row contributes, and the caller additionally gets each group's
# minimum input position so first-appearance output order (and distributed
# combines keyed on global row position) stay exact.

_SEGMENT_REDUCE_FNS = ("sum", "count", "min", "max")


@functools.lru_cache(maxsize=None)
def _segment_reduce_fn(num_lanes: int, col_fns: tuple[str, ...], engine: str = "xla"):
    from .merge import pack_selected, sorted_segments

    @jax.jit
    def f(key_lanes, pad_flag, pos, values, valids):
        m = pad_flag.shape[0]
        pad_sorted, perm, seg_start, _keep_last, seg_id = sorted_segments(
            num_lanes, 0, key_lanes, [], pad_flag, engine=engine
        )
        outs = []
        anyv = []
        for i, fn in enumerate(col_fns):
            v = values[i][perm]
            ok = valids[i][perm]
            if fn in ("sum", "count"):
                contrib = jnp.where(ok, v, jnp.zeros((), v.dtype))
                agg = jax.ops.segment_sum(contrib, seg_id, num_segments=m)
            else:
                is_max = fn == "max"
                if jnp.issubdtype(v.dtype, jnp.floating):
                    fill = jnp.finfo(v.dtype).min if is_max else jnp.finfo(v.dtype).max
                else:
                    fill = jnp.iinfo(v.dtype).min if is_max else jnp.iinfo(v.dtype).max
                masked = jnp.where(ok, v, fill)
                agg = (
                    jax.ops.segment_max(masked, seg_id, num_segments=m)
                    if is_max
                    else jax.ops.segment_min(masked, seg_id, num_segments=m)
                )
            outs.append(agg)
            anyv.append(jax.ops.segment_max(ok.astype(jnp.int32), seg_id, num_segments=m) > 0)
        first_pos = jax.ops.segment_min(pos[perm], seg_id, num_segments=m)
        packed, count = pack_selected(seg_start & (pad_sorted == 0), perm)
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

    Engines: "numpy" routes to the exact host twin; f64 columns leave the
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
    if engine == "pallas":
        from .pallas_kernels import note_dispatch

        note_dispatch(m)
    big = np.iinfo(np.int64).max
    outs, anyv, first_pos, packed, count = _segment_reduce_fn(k, tuple(fns), engine)(
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
