"""Key-lane compression: prefix truncation, lane packing, offset-value codes.

Every hot path in the system — merge read, compaction rewrite, sort-compact,
changelog dedup — bottoms out in one stable `lax.sort` over uint32 key lanes
(ops/merge.py), and sort cost scales with operand width. This module shrinks
that width with three order- and equality-preserving transforms, decided per
merge from lane statistics (a `LanePlan` alongside `MergePlan`):

  1. PREFIX TRUNCATION — a lane constant across the batch (the batch's shared
     key prefix: common int64 high words, a partition-constant string rank)
     affects neither ordering nor segmentation and is dropped outright.
     Partially-constant lanes are min-shifted so only their varying low bits
     remain (the bit-exact generalization of the old u16/u32 `narrow_lane`
     tiers): a lane spanning [lo, lo+2^b) carries exactly b bits.

  2. LANE PACKING — adjacent truncated lanes whose bit widths sum to <= 32
     fuse into ONE uint32 operand, most-significant lane in the high bits:
     unsigned comparison of the fused operand equals lexicographic comparison
     of its member lanes, and equality of the fused operand equals joint
     equality (the packing is injective because each member is < 2^bits).
     K logical lanes sort as ceil(sum bits / 32) physical operands.

  3. OVC LANES — "Robust and Efficient Sorting with Offset-Value Coding"
     (PAPERS.md) replaces full-key comparisons with (offset, value) codes
     computed once against a shared reference. Every input run of a merge
     (data file / memtable) is already key-sorted, so the batch minimum is
     the min over run heads — a row every input is >= of. Coding each row
     against that base, code = ((G - offset) << vbits) | value where offset
     is the first packed operand differing from the base and value is the
     row's operand there, yields a single uint32 lane with the OVC property:
     where two codes DIFFER, their unsigned order equals the rows' full key
     order; where they are EQUAL, the rows share their prefix through the
     offset operand and the sort falls through to the remaining operands.
     The code is therefore carried through `lax.sort` as the leading key
     (after the pad flag) without changing the output permutation, and
     segment boundary detection tests it FIRST — the overwhelming majority
     of adjacent-row comparisons resolve on the code lane alone instead of
     walking all key lanes. Computed on device (`ovc_codes_jax`) inside the
     merge kernels, with `ovc_codes_np` as the numpy oracle twin.

All three are pure reindexings of the comparator: sort order, tie structure
(stability), and the equal-key segmentation are bit-identical to the
uncompressed path — the parity suite (tests/test_lanes.py) asserts exactly
that across seeds, key shapes, null rates, and collation edge cases.

`merge.lane-compression` (default on) gates the whole layer; the
PAIMON_TPU_LANE_COMPRESSION env var overrides it in either direction so the
verify stages can force both paths.
"""

from __future__ import annotations

import os
import sys
from dataclasses import dataclass

import numpy as np

__all__ = [
    "LanePlan",
    "plan_lanes",
    "plan_lanes_from_stats",
    "plan_lanes_global",
    "lane_stats",
    "apply_plan",
    "compress_key_lanes",
    "compress_key_columns",
    "resolve_compress",
    "ovc_codes_np",
    "ovc_codes_jax",
    "scalar_dedup_winner",
]

# an OVC lane only pays when the packed key is still wide: G >= this many
# fused operands (a 1-operand key IS its own complete offset-value code)
_OVC_MIN_GROUPS = 2


@dataclass(frozen=True)
class LanePlan:
    """The per-merge compression decision over one (n, K) uint32 lane matrix.

    keep/los/bits describe truncation (kept original lane index, subtracted
    minimum, exact bit width after the shift); groups lists, per fused output
    operand, the positions INTO the kept sequence it packs (consecutive, in
    order, most-significant first). use_ovc adds the leading offset-value
    code lane, coded against `base` (the packed values of the batch's
    lexicographically minimal row) with a vbits-wide value field."""

    lanes_in: int
    keep: tuple[int, ...]
    los: tuple[int, ...]
    bits: tuple[int, ...]
    groups: tuple[tuple[int, ...], ...]
    use_ovc: bool = False
    ovc_vbits: int = 0
    base: tuple[int, ...] = ()

    @property
    def lanes_out(self) -> int:
        """Physical uint32 operands uploaded to the sort."""
        return len(self.groups)

    @property
    def sort_width(self) -> int:
        """Key operands the sort actually compares (incl. the OVC lane)."""
        return len(self.groups) + (1 if self.use_ovc else 0)

    @property
    def is_identity(self) -> bool:
        """True when applying the plan would be a no-op reshape: every lane
        kept, unshifted, alone in its group, no OVC."""
        return (
            not self.use_ovc
            and len(self.groups) == self.lanes_in
            and all(lo == 0 for lo in self.los)
            and all(len(g) == 1 for g in self.groups)
        )

    def upload_bytes_per_row(self) -> int:
        """Link bytes per row after the downstream u16/u32 narrowing tiers
        (ops/merge.narrow_lane picks u16 when a group's range fits)."""
        return sum(2 if sum(self.bits[p] for p in g) <= 16 else 4 for g in self.groups)


def resolve_compress(compress: bool | None) -> bool:
    """One resolution order everywhere: the PAIMON_TPU_LANE_COMPRESSION env
    var (verify stages force both paths) beats the caller's option value,
    which beats the default (on)."""
    env = os.environ.get("PAIMON_TPU_LANE_COMPRESSION", "").strip().lower()
    if env in ("0", "off", "false"):
        return False
    if env in ("1", "on", "true"):
        return True
    if compress is not None:
        return bool(compress)
    return True


def lane_stats(key_lanes: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Per-lane (min, max) over one shard's rows — the commutative piece a
    mesh merge reduces across shards before planning (plan_lanes_global).
    Zero-row shards contribute the neutral element (max sentinel mins, zero
    maxes), so reducing over them never widens a lane."""
    key_lanes = np.ascontiguousarray(key_lanes)
    n, k = key_lanes.shape
    if n == 0:
        return (
            np.full(k, 0xFFFFFFFF, dtype=np.uint32),
            np.zeros(k, dtype=np.uint32),
        )
    return key_lanes.min(axis=0), key_lanes.max(axis=0)


def _truncate_and_group(k: int, los, his, enable_ovc: bool = False):
    """The shared stats -> (keep, bits, lo_kept, groups, vbits, use_ovc)
    decision of every planner entry point: drop constant lanes, width each
    survivor to its exact ptp bit length, fuse adjacent widths into <=32-bit
    operands, and say whether an OVC lane pays (never unless enable_ovc)."""
    keep: list[int] = []
    bits: list[int] = []
    lo_kept: list[int] = []
    for i in range(k):
        ptp = int(his[i]) - int(los[i])
        if ptp:
            keep.append(i)
            bits.append(ptp.bit_length())
            lo_kept.append(int(los[i]))
    groups: list[tuple[int, ...]] = []
    cur: list[int] = []
    cur_bits = 0
    for pos, b in enumerate(bits):
        if cur and cur_bits + b > 32:
            groups.append(tuple(cur))
            cur, cur_bits = [], 0
        cur.append(pos)
        cur_bits += b
    if cur:
        groups.append(tuple(cur))
    vbits = max((sum(bits[p] for p in grp) for grp in groups), default=0)
    g = len(groups)
    use_ovc = enable_ovc and g >= _OVC_MIN_GROUPS and g.bit_length() + vbits <= 32
    if not use_ovc and all(len(grp) == 1 for grp in groups):
        # nothing fuses and no code lane needs a bounded value field: the
        # min-shift would be a pure copy (order and equality are shift-
        # invariant, and the upload tier re-shifts in narrow_lane anyway) —
        # zero the shifts so packing takes the no-arithmetic path
        lo_kept = [0] * len(lo_kept)
    return keep, bits, lo_kept, groups, vbits, use_ovc


def plan_lanes_from_stats(lanes_in: int, los, his) -> LanePlan:
    """Truncation + packing decided from per-lane (min, max) ALONE — the
    stats may have been reduced over many shards (plan_lanes_global), so
    every shard of one mesh merge derives identical packed widths and the
    packed operands stay comparable across devices (range-shuffle splitters,
    stacked shard_map lanes). Never emits an OVC lane: the code needs the
    batch-min row, and the mesh kernels carry plain packed lanes."""
    keep, bits, lo_kept, groups, _vbits, _ovc = _truncate_and_group(lanes_in, los, his)
    return LanePlan(lanes_in, tuple(keep), tuple(lo_kept), tuple(bits), tuple(groups))


def plan_lanes_global(parts) -> LanePlan:
    """ONE LanePlan for a whole mesh merge: reduce per-shard lane stats and
    plan from the reduction (ISSUE 7 satellite: per-shard plans can disagree
    on packed widths across devices — a lane spanning 8 bits on shard A and
    20 on shard B packs differently, and the stacked shard_map lanes or the
    range-shuffle splitters would then compare apples to oranges). Every
    shard applies THIS plan via apply_plan."""
    parts = [np.ascontiguousarray(p) for p in parts]
    k = parts[0].shape[1] if parts else 0
    if not parts or all(p.shape[0] == 0 for p in parts):
        return LanePlan(k, (), (), (), ())
    los = None
    his = None
    for p in parts:
        lo, hi = lane_stats(p)
        los = lo if los is None else np.minimum(los, lo)
        his = hi if his is None else np.maximum(his, hi)
    return plan_lanes_from_stats(k, los, his)


def plan_lanes(key_lanes: np.ndarray, enable_ovc: bool = True) -> LanePlan:
    """Decide truncation, packing, and OVC from one pass of lane stats.
    O(K * n) host work — the same order as the boundary compares it saves."""
    key_lanes = np.ascontiguousarray(key_lanes)
    n, k = key_lanes.shape
    if n <= 1 or k == 0:
        # 0/1 rows: every lane is batch-constant — a zero-width key
        return LanePlan(k, (), (), (), ())
    los, his = lane_stats(key_lanes)
    keep, bits, lo_kept, groups, vbits, use_ovc = _truncate_and_group(k, los, his, enable_ovc)
    base: tuple[int, ...] = ()
    if use_ovc:
        # the batch's lexicographically minimal row (over kept lanes), found
        # by iterative masking; its packed values are the shared OVC base —
        # a row every input row compares >= to, which is what makes the code
        # order-consistent
        mask = np.ones(n, dtype=np.bool_)
        min_vals: list[int] = []
        for i in keep:
            col = key_lanes[:, i]
            mval = int(col[mask].min())
            mask &= col == np.uint32(mval)
            min_vals.append(mval)
        packed_base = []
        for grp in groups:
            acc = 0
            for pos in grp:
                acc = (acc << bits[pos]) | (min_vals[pos] - lo_kept[pos])
            packed_base.append(acc)
        base = tuple(packed_base)
    return LanePlan(
        k, tuple(keep), tuple(lo_kept), tuple(bits), tuple(groups),
        use_ovc, vbits if use_ovc else 0, base,
    )


def apply_plan(plan: LanePlan, key_lanes: np.ndarray) -> np.ndarray:
    """(n, K) uint32 -> (n, lanes_out) uint32: shift and fuse per the plan.
    Order-, equality-, and stability-preserving by construction (see module
    docstring); the numpy half of the transform — the OVC lane is computed
    from THIS output, on device in the kernels or via ovc_codes_np on the
    oracle path."""
    key_lanes = np.ascontiguousarray(key_lanes)
    n = key_lanes.shape[0]
    if all(len(g) == 1 for g in plan.groups) and not any(plan.los):
        # pure truncation: a column selection, no per-row arithmetic
        if len(plan.groups) == plan.lanes_in:
            return key_lanes.astype(np.uint32, copy=False)
        sel = [plan.keep[g[0]] for g in plan.groups]
        return np.ascontiguousarray(key_lanes[:, sel].astype(np.uint32, copy=False))
    return _pack(plan, n, lambda i: key_lanes[:, i].astype(np.uint32))


def _pack(plan: LanePlan, n: int, lane) -> np.ndarray:
    """The plan's shift-and-fuse arithmetic over logical lanes fetched one at
    a time: lane(i) is a fresh (n,) uint32 array of logical lane i, cut from
    a lane matrix (apply_plan) or made from a key column
    (compress_key_columns). A sole group is the result itself, reshaped."""
    g = len(plan.groups)
    out = None if g == 1 else np.empty((n, g), dtype=np.uint32)
    for gi, grp in enumerate(plan.groups):
        acc = None
        for pos in grp:
            v = lane(plan.keep[pos])
            if plan.los[pos]:
                v -= np.uint32(plan.los[pos])
            acc = v if acc is None else (acc << np.uint32(plan.bits[pos])) | v
        if out is None:
            return acc.reshape(n, 1)
        out[:, gi] = acc
    return out


def compress_key_lanes(
    key_lanes: np.ndarray,
    compress: bool | None = None,
    enable_ovc: bool = True,
) -> tuple[np.ndarray, LanePlan | None]:
    """The one seam every consumer calls: returns (lanes', plan) where lanes'
    is the compressed (n, G) matrix, or (lanes, None) unchanged when the
    layer is off. Records the lanes{...} metric group per planned merge."""
    if not resolve_compress(compress):
        return key_lanes, None
    from ..metrics import span

    with span("lanes.compress", rows=key_lanes.shape[0], lanes_in=key_lanes.shape[1]) as sp:
        key_lanes = np.ascontiguousarray(key_lanes)
        plan = plan_lanes(key_lanes, enable_ovc=enable_ovc)
        packed = apply_plan(plan, key_lanes)
        sp.add(lanes_out=packed.shape[1])
    _record(plan, key_lanes.shape[0])
    return packed, plan


def compress_key_columns(
    columns,
    compress: bool | None = None,
    enable_ovc: bool = True,
) -> tuple[np.ndarray, LanePlan] | None:
    """The seam's entry for callers that still hold the key COLUMNS, each a
    fixed-width signed integer array (data.keys.integer_key_columns: int64
    is two logical lanes, a narrower column one): the same (lanes', plan) as
    compress_key_lanes over the encoded (n, K) matrix, bit for bit, made in
    one pass — a column's min and max give every lane's, and each packed
    operand is written straight from the columns — so that neither the
    matrix nor its re-packing exists. Declines with None, and counts
    nothing, where the layer is off or the plan would carry an OVC lane
    (its base is the batch-minimum ROW, found on the matrix): the caller
    encodes the matrix and takes compress_key_lanes as before."""
    if not resolve_compress(compress):
        return None
    columns = [np.ascontiguousarray(c) for c in columns]
    lanes = [(c, part) for c in columns for part in range(1 if c.dtype.itemsize <= 4 else 2)]
    n, k = (len(columns[0]) if columns else 0), len(lanes)
    if n <= 1 or k == 0:
        plan = LanePlan(k, (), (), (), ())  # as plan_lanes: a zero-width key
    else:
        stats = [s for c in columns for s in _column_lane_stats(c)]
        keep, bits, lo_kept, groups, _vbits, use_ovc = _truncate_and_group(
            k, [lo for lo, _ in stats], [hi for _, hi in stats], enable_ovc
        )
        if use_ovc:
            return None
        plan = LanePlan(k, tuple(keep), tuple(lo_kept), tuple(bits), tuple(groups))
    packed = _pack(plan, n, lambda i: _column_lane(*lanes[i]))
    _record(plan, n, from_columns=True)
    return packed, plan


_LOW_WORD = 0 if sys.byteorder == "little" else 1


def _column_lane_stats(col: np.ndarray) -> list[tuple[int, int]]:
    """(min, max) of each logical lane of one integer key column, as
    lane_stats reads them off the encoded matrix, from the column's own min
    and max: the sign-flipped encoding is monotone in the value, and so is an
    int64's high word; its low word spans the column's where the high word
    is constant and otherwise costs one more pass over the column."""
    a, b = int(col.min()), int(col.max())
    if col.dtype.itemsize <= 4:
        return [(a + (1 << 31), b + (1 << 31))]
    ua, ub = a + (1 << 63), b + (1 << 63)
    if ua >> 32 == ub >> 32:
        return [(ua >> 32, ub >> 32), (ua & 0xFFFFFFFF, ub & 0xFFFFFFFF)]
    low = col.view(np.uint32)[_LOW_WORD::2]
    return [(ua >> 32, ub >> 32), (int(low.min()), int(low.max()))]


def _column_lane(col: np.ndarray, part: int) -> np.ndarray:
    """Logical lane `part` of an integer key column as a fresh (n,) uint32
    array: element for element what data.keys._encode_column writes."""
    if col.dtype.itemsize <= 4:
        return col.astype(np.int32, copy=False).view(np.uint32) ^ np.uint32(0x80000000)
    u = col.view(np.uint64)
    if part:
        return u.astype(np.uint32)  # the low word: the sign flip is bit 63
    return (u >> np.uint64(32)).astype(np.uint32) ^ np.uint32(0x80000000)


def _record(plan: LanePlan, n: int, from_columns: bool = False) -> None:
    from ..metrics import lanes_metrics

    g = lanes_metrics()
    g.counter("plans").inc()
    g.counter("plans_from_columns").inc(int(from_columns))
    g.counter("lanes_in").inc(plan.lanes_in)
    g.counter("lanes_out").inc(plan.sort_width)
    if plan.use_ovc:
        g.counter("ovc_merges").inc()
    g.counter("bytes_saved").inc(max(0, n * (4 * plan.lanes_in - plan.upload_bytes_per_row())))


# ---------------------------------------------------------------------------
# offset-value codes
# ---------------------------------------------------------------------------

def ovc_codes_np(packed: np.ndarray, base, vbits: int) -> np.ndarray:
    """Numpy oracle of the OVC kernel: packed (n, G) uint32 operands, base
    (G,) packed values of a row <= every input row. Returns (n,) uint32
    codes ((G - offset) << vbits) | value; a row equal to the base codes 0."""
    packed = np.ascontiguousarray(packed, dtype=np.uint32)
    n, g = packed.shape
    base = np.asarray(base, dtype=np.uint32)
    eq = packed == base[None, :]
    prefix = np.cumprod(eq, axis=1).astype(bool)  # still-equal through lane j
    offset = prefix.sum(axis=1).astype(np.int64)  # first differing operand; G = equal
    first_diff = np.minimum(offset, g - 1)
    value = packed[np.arange(n), first_diff]
    value = np.where(offset < g, value, np.uint32(0)).astype(np.uint32)
    return ((np.uint32(g) - offset.astype(np.uint32)) << np.uint32(vbits)) | value


def ovc_codes_jax(lanes, base, vbits: int):
    """Device twin of ovc_codes_np, traced inside the merge kernels: lanes is
    a sequence of (m,) uint arrays (possibly narrowed u16 — upcast is free on
    device), base a (G,) uint32 array. Pad rows produce one shared (garbage)
    code; the pad flag leads both the sort and the boundary compare, so pad
    codes never order or segment anything."""
    import jax.numpy as jnp

    g = len(lanes)
    m = lanes[0].shape[0]
    eq_run = jnp.ones(m, dtype=jnp.bool_)
    offset = jnp.zeros(m, dtype=jnp.uint32)
    value = jnp.zeros(m, dtype=jnp.uint32)
    for j in range(g):
        l32 = lanes[j].astype(jnp.uint32)
        bj = base[j].astype(jnp.uint32)
        first_diff = eq_run & (l32 != bj)
        value = jnp.where(first_diff, l32, value)
        eq_run = eq_run & (l32 == bj)
        offset = offset + eq_run.astype(jnp.uint32)
    return ((jnp.uint32(g) - offset) << jnp.uint32(vbits)) | value


# ---------------------------------------------------------------------------
# zero-width fast path
# ---------------------------------------------------------------------------

def scalar_dedup_winner(seq_lanes: np.ndarray | None, n: int) -> np.ndarray:
    """All keys equal (every lane batch-constant): dedup degenerates to ONE
    winner — the last row in (sequence lanes, input order). No key sort, no
    device trip; the zero-width scalar fast path of ISSUE 6."""
    if n == 0:
        return np.empty(0, dtype=np.int32)
    if seq_lanes is None or seq_lanes.shape[1] == 0:
        return np.array([n - 1], dtype=np.int32)
    order = np.lexsort([seq_lanes[:, i] for i in range(seq_lanes.shape[1] - 1, -1, -1)])
    return order[-1:].astype(np.int32)
