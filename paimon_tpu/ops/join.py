"""Device-side skew-aware equi-joins: hash + sort-merge over uint32 key lanes.

The JSPIM move (PAPERS.md) for this codebase: JOIN becomes the same shape as
every merge — normalized uint32 key lanes, one stable device sort, segment
reductions — instead of a host hash table probed row at a time. The pieces
deliberately reuse the merge machinery so joins inherit every optimization
that landed for merges:

  * key encoding rides `data/keys.py` — typed columns become order- and
    equality-preserving uint32 lanes; string/bytes keys rank against one
    pool built over BOTH sides (exact, collision-free);
  * lane compression rides `ops/lanes.py` — one GLOBAL `LanePlan` over both
    sides (the ISSUE 7 rule: per-side plans would pack incomparably)
    truncates and packs the lanes, so a composite key often joins as a
    single fused uint32 operand;
  * the sort-merge kernel rides the `ops/merge.sorted_segments` seam — the
    build and probe rows concatenate with a side lane as the leading
    sequence lane, one stable sort groups equal keys into segments with
    build rows first;
  * the code domain rides `ops/dicts.py` — when both sides of a key column
    are dictionary-backed, their pools unify once (O(|pool|) object work)
    and the join matches remapped uint32 codes with ZERO string
    materialization end to end (`join{code_domain_joins}`), falling back
    per join past `merge.dict-domain.pool-limit`.

Skew (the JSPIM headline): one hot probe key must not serialize a
partition. When the probe side is large enough to split (`join.chunk-rows`,
or an explicit `join.partitions`), a key-histogram pass over the probe
lanes finds heavy hitters (probe share >= `join.skew-factor` x the fair
per-partition share); light keys hash-partition both sides as usual, heavy
keys SPLIT their probe rows round-robin across every partition and
replicate their (few) build rows to each — each probe row still meets each
matching build row exactly once, and no partition is left holding the hot
key alone (`join{skew_keys, skew_split_rows}`).

Two tiers:

  * `join_batches` — the full two-batch join (SQL `JOIN`, benchmarks):
    per-query encoding, global lane plan, skew partitioning, device or
    numpy kernels; output pairs ordered by (probe row, build row).
  * `JoinIndex` — a cached build-side structure for repeated probes
    (lookup tables): build lanes encode once per refresh, fold to <= 64-bit
    codes, and each probe batch pays one searchsorted — the vectorized
    replacement for the per-row `FullCacheLookupTable.get` loop. Probe
    values absent from the build pools are masked exactly (never a false
    match), so probe-side misses need no shared pool.

Both tiers produce BIT-IDENTICAL output to the host oracle (numpy/pandas)
across seeds, skew, null rates, dict/non-dict and lane-compression on/off —
tests/test_join.py pins exactly that. NULL join keys never match (SQL
semantics): inner drops them, left emits the row unmatched.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass, field
from typing import Mapping, Sequence

import numpy as np

from ..types import TypeRoot

__all__ = [
    "JoinError",
    "JoinResult",
    "JoinIndex",
    "join_batches",
    "materialize_join",
    "resolve_join_engine",
    "partition_executor",
]

_STRING_ROOTS = (TypeRoot.CHAR, TypeRoot.VARCHAR, TypeRoot.BINARY, TypeRoot.VARBINARY)

# distributed-partition seam (ISSUE 15, declared PR 12 follow-up): when an
# executor is installed, the skew-planned per-partition kernels run through
# it — the cluster client routes partition i to the worker owning bucket
# (i % num_buckets), so the JSPIM split spans worker processes. The executor
# receives [(probe_lanes, build_lanes, algorithm, engine), ...] and returns
# the per-partition (left_take, right_take) index pairs, which compose into
# a JoinResult bit-identical to the local loop (partition order preserved).
import contextlib
import contextvars

_PART_EXECUTOR: "contextvars.ContextVar" = contextvars.ContextVar(
    "paimon_tpu_join_part_executor", default=None
)


@contextlib.contextmanager
def partition_executor(fn):
    """Install `fn([(ll, rl, algorithm, engine), ...]) -> [(lt, rt), ...]`
    as the join-partition executor for the calling context."""
    token = _PART_EXECUTOR.set(fn)
    try:
        yield
    finally:
        _PART_EXECUTOR.reset(token)


class JoinError(ValueError):
    pass


def _metrics():
    from ..metrics import join_metrics

    return join_metrics()


# ---------------------------------------------------------------------------
# option / engine resolution
# ---------------------------------------------------------------------------

def _opt(options, key: str, default):
    """join.* options arrive as a table Options object, a plain str->str
    mapping (SQL hints), or None."""
    if options is None:
        return default
    get = getattr(options, "to_map", None)
    data = get() if get is not None else options
    v = data.get(key)
    if v is None:
        return default
    if isinstance(default, bool):
        return str(v).strip().lower() in ("1", "on", "true")
    if isinstance(default, int):
        return int(v)
    if isinstance(default, float):
        return float(v)
    return str(v)


def resolve_join_engine(options=None, rows: int = 0) -> str:
    """'numpy' | 'xla'. Resolution mirrors the merge kernels
    (core/mergefn.effective_sort_engine): the PAIMON_TPU_JOIN_ENGINE env
    (test forcing knob) beats the `join.engine` option beats auto. Auto
    keeps small joins on the host lexsort (dispatch overhead dominates) and
    CPU-only platforms host-side unless PAIMON_TPU_FORCE_DEVICE_ENGINE
    pins the device path."""
    env = os.environ.get("PAIMON_TPU_JOIN_ENGINE", "").strip().lower()
    choice = env or _opt(options, "join.engine", "auto")
    if choice in ("xla", "xla-segmented"):
        return "xla"
    if choice == "numpy":
        return choice
    if choice != "auto":
        raise ValueError(f"join.engine: {choice!r} is not one of 'auto', 'numpy', 'xla'")
    if rows < _opt(options, "join.device-rows", 4096):
        return "numpy"
    from .merge import resolved_platform_is_cpu

    if resolved_platform_is_cpu() and os.environ.get("PAIMON_TPU_FORCE_DEVICE_ENGINE", "") != "1":
        return "numpy"
    return "xla"


# ---------------------------------------------------------------------------
# key encoding: typed columns (both sides) -> comparable uint32 lanes
# ---------------------------------------------------------------------------

@dataclass
class _EncodedKeys:
    left: np.ndarray  # (n_l, L) uint32
    right: np.ndarray  # (n_r, L) uint32
    left_live: np.ndarray  # bool — non-null key, eligible to match
    right_live: np.ndarray
    code_domain_cols: int = 0  # key columns matched in the code domain


def _null_filled_values(col, pool):
    """Object values with nulls replaced by a harmless present value (the
    validity mask already bars those rows from matching; the substitute
    only keeps the pool ranking total)."""
    values = col.values
    if col.validity is None:
        return values
    values = values.copy()
    values[~col.validity] = pool[0] if len(pool) else ""
    return values


def _present_string_pool(cols) -> np.ndarray:
    """Sorted distinct PRESENT values across the given string columns —
    exact_string_pool, except NULL slots (join keys may be nullable, unlike
    merge keys) are dropped before the pool builds."""
    from ..data.keys import build_string_pool, exact_string_pool
    from .dicts import cache_usable

    cols = list(cols)
    if cols and all(cache_usable(c) for c in cols):
        return exact_string_pool(cols)  # prunes through validity already
    parts = []
    for c in cols:
        v = c.values
        if c.validity is not None:
            v = v[c.validity]
        parts.append(v)
    return build_string_pool(parts)


def _try_code_domain(lc, rc, limit) -> tuple[np.ndarray, np.ndarray] | None:
    """One key column pair in the code domain: both sides dictionary-backed
    -> unify the two pools and remap both code vectors (ops.dicts). Returns
    (left_lane, right_lane) uint32 or None (expanded fallback)."""
    from .dicts import cache_usable, remap_codes, resolve_pool_limit, unify_pools

    if not (cache_usable(lc) and cache_usable(rc)):
        return None
    lp, lcodes = lc.dict_cache
    rp, rcodes = rc.dict_cache
    if len(lp) + len(rp) > resolve_pool_limit(limit):
        return None
    unified, (lmap, rmap) = unify_pools([lp, rp])
    if len(unified) > resolve_pool_limit(limit):
        return None
    return remap_codes(lmap, lcodes), remap_codes(rmap, rcodes)


def _encode_join_keys(left, right, left_keys, right_keys, pool_limit=None) -> _EncodedKeys:
    """Shared-space lanes for the key columns of both sides. Equality of the
    lane tuples == typed equality of the key tuples (the data/keys.py
    contract), with string ranks taken against ONE pool covering both
    sides — or, when both sides are dictionary-backed, against the unified
    code domain with zero string materialization."""
    from ..data.keys import _encode_column

    if len(left_keys) != len(right_keys) or not left_keys:
        raise JoinError(f"key arity mismatch: {list(left_keys)} vs {list(right_keys)}")
    n_l, n_r = left.num_rows, right.num_rows
    left_live = np.ones(n_l, dtype=np.bool_)
    right_live = np.ones(n_r, dtype=np.bool_)
    lanes_l: list[np.ndarray] = []
    lanes_r: list[np.ndarray] = []
    code_cols = 0
    for lname, rname in zip(left_keys, right_keys):
        lf, rf = left.schema.field(lname), right.schema.field(rname)
        if lf.type.root != rf.type.root:
            raise JoinError(
                f"join key type mismatch: {lname} is {lf.type.root}, {rname} is {rf.type.root}"
            )
        lc, rc = left.column(lname), right.column(rname)
        if lc.validity is not None:
            left_live &= lc.validity
        if rc.validity is not None:
            right_live &= rc.validity
        coded = _try_code_domain(lc, rc, pool_limit)
        if coded is not None:
            lanes_l.append(coded[0].astype(np.uint32, copy=False))
            lanes_r.append(coded[1].astype(np.uint32, copy=False))
            code_cols += 1
            continue
        root = lf.type.root
        if root in _STRING_ROOTS:
            pool = _present_string_pool([lc, rc])
            if len(pool) == 0:  # every key NULL on both sides: no row matches
                lanes_l.append(np.zeros(n_l, dtype=np.uint32))
                lanes_r.append(np.zeros(n_r, dtype=np.uint32))
                left_live &= False
                right_live &= False
                continue
            lanes_l.extend(_encode_column(_null_filled_values(lc, pool), root, pool))
            lanes_r.extend(_encode_column(_null_filled_values(rc, pool), root, pool))
        else:
            lanes_l.extend(_encode_column(lc.values, root, None))
            lanes_r.extend(_encode_column(rc.values, root, None))
    stack = lambda ls, n: (  # noqa: E731 — tiny local
        np.stack(ls, axis=1).astype(np.uint32, copy=False)
        if ls
        else np.zeros((n, 0), dtype=np.uint32)
    )
    return _EncodedKeys(stack(lanes_l, n_l), stack(lanes_r, n_r), left_live, right_live, code_cols)


# ---------------------------------------------------------------------------
# kernels: hash probe (single lane) and sort-merge (multi lane)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _hash_probe_fn():
    """Jitted single-lane probe: stable-sort the build lane (pads, filled
    with the u32 max sentinel, sort last), binary-search every probe value,
    clip the hit range to the valid build prefix so a real key equal to the
    sentinel can never count pad rows. Downloads O(n) int32 — the expansion
    to pairs is host numpy."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(build_pad, build_lane, probe_lane, nr):
        m = build_lane.shape[0]
        iota = jnp.arange(m, dtype=jnp.int32)
        _, sl, order = jax.lax.sort([build_pad, build_lane, iota], num_keys=2, is_stable=True)
        lo = jnp.searchsorted(sl, probe_lane, side="left").astype(jnp.int32)
        hi = jnp.searchsorted(sl, probe_lane, side="right").astype(jnp.int32)
        lo = jnp.minimum(lo, nr)
        hi = jnp.minimum(hi, nr)
        return order, lo, hi - lo

    return f


def _hash_pairs(ll: np.ndarray, rl: np.ndarray, engine: str):
    """Single-lane equi-join core: (probe_counts, probe_starts, mapping)
    where mapping[sorted_pos] = build row and each probe row's matches are
    mapping[starts : starts+counts] (build rows ascending)."""
    n_l, n_r = ll.shape[0], rl.shape[0]
    lane_l, lane_r = ll[:, 0], rl[:, 0]
    if engine == "numpy" or n_r == 0 or n_l == 0:
        order = np.argsort(lane_r, kind="stable").astype(np.int64)
        dom = int(max(lane_r.max() if n_r else 0, lane_l.max() if n_l else 0)) + 1
        if 0 < dom <= max(1 << 20, 4 * (n_l + n_r)):
            # dense domain (dictionary codes, min-shifted lanes): direct
            # addressing — two O(n) gathers instead of two 1M-row binary
            # searches. bincount + exclusive cumsum IS the hash table.
            counts_k = np.bincount(lane_r, minlength=dom)
            starts_k = np.concatenate([[0], np.cumsum(counts_k)[:-1]])
            return (
                counts_k[lane_l].astype(np.int64),
                starts_k[lane_l].astype(np.int64),
                order,
            )
        srt = lane_r[order]
        lo = np.searchsorted(srt, lane_l, side="left")
        hi = np.searchsorted(srt, lane_l, side="right")
        return (hi - lo).astype(np.int64), lo.astype(np.int64), order
    from .merge import pad_size

    m_r, m_l = pad_size(n_r), pad_size(n_l)
    bpad = np.zeros(m_r, dtype=np.uint8)
    bpad[n_r:] = 1
    blane = np.full(m_r, 0xFFFFFFFF, dtype=np.uint32)
    blane[:n_r] = lane_r
    plane = np.zeros(m_l, dtype=np.uint32)
    plane[:n_l] = lane_l
    order, lo, counts = _hash_probe_fn()(bpad, blane, plane, np.int32(n_r))
    return (
        np.asarray(counts)[:n_l].astype(np.int64),
        np.asarray(lo)[:n_l].astype(np.int64),
        np.asarray(order).astype(np.int64),
    )


def _sortmerge_pairs(ll: np.ndarray, rl: np.ndarray, engine: str):
    """Multi-lane equi-join core through the ONE merge preamble: concat
    [build; probe] rows, sort by (key lanes, side, input order) via
    `sorted_segments` (device) or np.lexsort (host), segment by key. Build
    rows lead each segment (side lane 0 < 1), so a probe row's matches are
    the first right_count slots of its segment. Returns (counts, starts,
    mapping) in the same contract as _hash_pairs — mapping is the sorted
    permutation, whose build slots hold build row indices directly."""
    n_r, n_l = rl.shape[0], ll.shape[0]
    n = n_r + n_l
    k = ll.shape[1]
    joint = np.vstack([rl, ll])
    side = np.zeros(n, dtype=np.uint32)
    side[n_r:] = 1
    if engine == "numpy" or n == 0:
        keys = [side] + [joint[:, i] for i in range(k - 1, -1, -1)]
        perm = np.lexsort(keys).astype(np.int64)
        srt = joint[perm]
        neq = (srt[1:] != srt[:-1]).any(axis=1) if n > 1 else np.zeros(0, dtype=bool)
        seg = np.concatenate([[0], np.cumsum(neq)]).astype(np.int64) if n else np.zeros(0, np.int64)
    else:
        from .merge import _merge_plan_padded

        plan = _merge_plan_padded(joint, side[:, None])
        perm = plan.perm[:n].astype(np.int64)
        seg = plan.seg_id[:n].astype(np.int64)
    is_left = perm >= n_r
    num_segs = int(seg[-1]) + 1 if n else 0
    seg_start = np.searchsorted(seg, np.arange(num_segs))
    right_count = np.bincount(seg[~is_left], minlength=num_segs) if n else np.zeros(0, np.int64)
    left_slots = np.flatnonzero(is_left)
    left_inputs = perm[left_slots] - n_r
    lsegs = seg[left_slots]
    counts = np.zeros(n_l, dtype=np.int64)
    starts = np.zeros(n_l, dtype=np.int64)
    counts[left_inputs] = right_count[lsegs]
    starts[left_inputs] = seg_start[lsegs]
    return counts, starts, perm


def _expand_pairs(counts: np.ndarray, starts: np.ndarray, mapping: np.ndarray):
    """(per-probe counts, per-probe start into mapping) -> flat (left, right)
    index pairs, probe-major, build rows ascending within each probe row."""
    n_l = counts.shape[0]
    total = int(counts.sum())
    if total == 0:
        return np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int64)
    if total <= n_l and counts.max() <= 1:
        # unique build keys (the PK-dimension case): no fan-out, the pair
        # list is just the matched probe rows — skip the repeat machinery
        lt = np.flatnonzero(counts).astype(np.int64)
        return lt, mapping[starts[lt]]
    lt = np.repeat(np.arange(n_l, dtype=np.int64), counts)
    cumex = np.concatenate([[0], np.cumsum(counts)[:-1]])
    offs = np.arange(total, dtype=np.int64) - np.repeat(cumex, counts) + np.repeat(starts, counts)
    return lt, mapping[offs]


def _join_part(ll: np.ndarray, rl: np.ndarray, algorithm: str, engine: str):
    """Inner-join one partition of live rows; returns (lt, rt) local pairs."""
    n_l, n_r = ll.shape[0], rl.shape[0]
    if n_l == 0 or n_r == 0:
        return np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int64)
    if ll.shape[1] == 0:
        # zero-width key (batch-constant on both sides): every live probe row
        # matches every live build row — the degenerate cross product
        lt = np.repeat(np.arange(n_l, dtype=np.int64), n_r)
        rt = np.tile(np.arange(n_r, dtype=np.int64), n_l)
        return lt, rt
    if algorithm == "hash" and ll.shape[1] == 1:
        counts, starts, mapping = _hash_pairs(ll, rl, engine)
    else:
        counts, starts, mapping = _sortmerge_pairs(ll, rl, engine)
    return _expand_pairs(counts, starts, mapping)


# ---------------------------------------------------------------------------
# skew-aware partitioning (JSPIM)
# ---------------------------------------------------------------------------

def _key_ids(left_lanes: np.ndarray, right_lanes: np.ndarray):
    """Joint dense key ids over both sides (void-view unique: one host pass,
    no per-row python). Returns (left_ids, right_ids, num_keys)."""
    k = left_lanes.shape[1]
    joint = np.ascontiguousarray(np.vstack([left_lanes, right_lanes]))
    if k == 0:
        return (
            np.zeros(left_lanes.shape[0], dtype=np.int64),
            np.zeros(right_lanes.shape[0], dtype=np.int64),
            1,
        )
    if k == 1:  # single fused operand: a plain u32 sort, no void-row compares
        _, inv = np.unique(joint[:, 0], return_inverse=True)
    else:
        view = joint.view([("", np.uint32)] * k).ravel()
        _, inv = np.unique(view, return_inverse=True)
    inv = inv.astype(np.int64)
    return inv[: left_lanes.shape[0]], inv[left_lanes.shape[0]:], int(inv.max()) + 1 if len(inv) else 0


@dataclass
class _SkewPlan:
    parts: list[tuple[np.ndarray, np.ndarray]]  # per partition: (probe idx, build idx)
    skew_keys: int = 0
    skew_split_rows: int = 0


def _plan_partitions(
    left_lanes, right_lanes, live_l: np.ndarray, live_r: np.ndarray,
    num_parts: int, skew_factor: float,
) -> _SkewPlan:
    """Split live probe/build rows into num_parts key-disjoint partitions,
    except for heavy hitters: a key holding >= skew_factor x the fair
    per-partition probe share gets its probe rows dealt round-robin across
    ALL partitions and its build rows replicated to each — the JSPIM skew
    split. Build rows whose key never appears live on the probe side are
    dropped (they cannot match under inner OR left semantics)."""
    li = np.flatnonzero(live_l)
    ri = np.flatnonzero(live_r)
    lid, rid, nk = _key_ids(left_lanes[li], right_lanes[ri])
    n_live = len(li)
    probe_counts = np.bincount(lid, minlength=max(nk, 1))
    # a key's probe rows cannot be subdivided by hashing, so any key holding
    # a meaningful fraction of one partition's fair share already skews that
    # partition — split it (the threshold is in units of the fair share)
    heavy_cut = max(skew_factor * n_live / max(num_parts, 1), 2.0)
    heavy = probe_counts >= heavy_cut
    if num_parts <= 1:
        heavy[:] = False
    # key -> partition for light keys (Knuth multiplicative spread)
    key_part = (np.arange(len(probe_counts), dtype=np.uint64) * np.uint64(2654435761)) % np.uint64(num_parts)
    l_heavy = heavy[lid]
    l_part = key_part[lid].astype(np.int64)
    # heavy probe rows: round-robin deal, per-row position within its key
    if l_heavy.any():
        l_part[l_heavy] = np.arange(int(l_heavy.sum()), dtype=np.int64) % num_parts
    r_matched = probe_counts[rid] > 0 if len(rid) else np.zeros(0, dtype=bool)
    r_heavy = heavy[rid] & r_matched if len(rid) else np.zeros(0, dtype=bool)
    r_part = key_part[rid].astype(np.int64) if len(rid) else np.zeros(0, np.int64)
    parts: list[tuple[np.ndarray, np.ndarray]] = []
    heavy_build = ri[r_heavy]
    for p in range(num_parts):
        probe_p = li[l_part == p]
        build_p = ri[r_matched & ~r_heavy & (r_part == p)]
        if len(heavy_build):
            build_p = np.sort(np.concatenate([build_p, heavy_build]))
        parts.append((probe_p, build_p))
    return _SkewPlan(
        parts,
        skew_keys=int(heavy.sum()),
        skew_split_rows=int(l_heavy.sum()),
    )


# ---------------------------------------------------------------------------
# the full two-batch join
# ---------------------------------------------------------------------------

@dataclass
class JoinResult:
    """Flat matched pairs, probe-major: left_take ascending (stable), build
    rows ascending within each probe row. right_take is -1 where a LEFT
    join kept an unmatched probe row."""

    left_take: np.ndarray
    right_take: np.ndarray
    n_left: int
    n_right: int
    how: str = "inner"
    stats: dict = field(default_factory=dict)

    @property
    def matched(self) -> np.ndarray:
        return self.right_take >= 0

    @property
    def num_rows(self) -> int:
        return len(self.left_take)


def join_batches(
    left,
    right,
    left_keys: Sequence[str],
    right_keys: Sequence[str],
    how: str = "inner",
    options: "Mapping | None" = None,
    engine: str | None = None,
) -> JoinResult:
    """Equi-join two ColumnBatches on aligned key column lists.

    how='inner' keeps matched pairs; how='left' additionally emits every
    unmatched probe row once with right_take == -1. NULL keys never match.
    Output order is deterministic: probe rows in input order, each probe
    row's matches in build input order — the same order a host nested loop
    (and the pandas oracle in the parity suite) produces."""
    import time as _time

    if how not in ("inner", "left"):
        raise JoinError(f"unsupported join type {how!r} (inner | left)")
    g = _metrics()
    t0 = _time.perf_counter()
    enc = _encode_join_keys(
        left, right, list(left_keys), list(right_keys),
        pool_limit=_opt(options, "merge.dict-domain.pool-limit", None) if options else None,
    )
    n_l, n_r = left.num_rows, right.num_rows
    engine = engine or resolve_join_engine(options, rows=n_l + n_r)
    from .lanes import plan_lanes_global, apply_plan, resolve_compress

    comp_opt = _opt(options, "merge.lane-compression", True) if options is not None else None
    if resolve_compress(comp_opt):
        plan = plan_lanes_global([enc.left, enc.right])
        ll = apply_plan(plan, enc.left)
        rl = apply_plan(plan, enc.right)
    else:
        ll, rl = enc.left, enc.right
    algorithm = _opt(options, "join.algorithm", "auto")
    if algorithm == "auto":
        algorithm = "hash" if ll.shape[1] == 1 else "sort-merge"
    elif algorithm == "hash" and ll.shape[1] != 1:
        algorithm = "sort-merge"  # hash needs a single fused operand
    chunk_rows = _opt(options, "join.chunk-rows", 1 << 20)
    num_parts = _opt(options, "join.partitions", 0)
    if num_parts <= 0:
        num_parts = max(1, -(-n_l // max(chunk_rows, 1)))
    skew_factor = _opt(options, "join.skew-factor", 0.5)
    t_build = _time.perf_counter()

    if num_parts > 1:
        plan_p = _plan_partitions(ll, rl, enc.left_live, enc.right_live, num_parts, skew_factor)
        lt_all, rt_all = [], []
        part_exec = _PART_EXECUTOR.get()
        if part_exec is not None:
            pairs = part_exec(
                [(ll[pi], rl[bi], algorithm, engine) for pi, bi in plan_p.parts]
            )
            for (probe_idx, build_idx), (lt, rt) in zip(plan_p.parts, pairs):
                lt_all.append(probe_idx[lt])
                rt_all.append(build_idx[rt])
        else:
            for probe_idx, build_idx in plan_p.parts:
                lt, rt = _join_part(ll[probe_idx], rl[build_idx], algorithm, engine)
                lt_all.append(probe_idx[lt])
                rt_all.append(build_idx[rt])
        lt_g = np.concatenate(lt_all) if lt_all else np.empty(0, np.int64)
        rt_g = np.concatenate(rt_all) if rt_all else np.empty(0, np.int64)
        skew_keys, skew_rows = plan_p.skew_keys, plan_p.skew_split_rows
    else:
        li = np.flatnonzero(enc.left_live)
        ri = np.flatnonzero(enc.right_live)
        if len(li) == n_l and len(ri) == n_r:
            lt_g, rt_g = _join_part(ll, rl, algorithm, engine)
        else:
            lt, rt = _join_part(ll[li], rl[ri], algorithm, engine)
            lt_g, rt_g = li[lt], ri[rt]
        skew_keys = skew_rows = 0

    sorted_already = num_parts == 1  # _expand_pairs emits probe-major order
    if how == "left":
        matched = np.zeros(n_l, dtype=bool)
        matched[lt_g] = True
        miss = np.flatnonzero(~matched)
        if len(miss):
            lt_g = np.concatenate([lt_g, miss])
            rt_g = np.concatenate([rt_g, np.full(len(miss), -1, dtype=np.int64)])
            sorted_already = False
    if not sorted_already:
        order = np.argsort(lt_g, kind="stable")
        lt_g, rt_g = lt_g[order], rt_g[order]
    res = JoinResult(
        left_take=lt_g,
        right_take=rt_g,
        n_left=n_l,
        n_right=n_r,
        how=how,
        stats={
            "algorithm": algorithm,
            "engine": engine,
            "partitions": num_parts,
            "skew_keys": skew_keys,
            "skew_split_rows": skew_rows,
            "code_domain_cols": enc.code_domain_cols,
            "lanes": ll.shape[1],
        },
    )
    g.counter("joins").inc()
    g.counter("rows_probed").inc(n_l)
    g.counter("rows_matched").inc(int(res.matched.sum()))
    g.counter("hash_joins" if algorithm == "hash" else "sort_merge_joins").inc()
    if enc.code_domain_cols:
        g.counter("code_domain_joins").inc()
    if skew_keys:
        g.counter("skew_keys").inc(skew_keys)
        g.counter("skew_split_rows").inc(skew_rows)
    g.histogram("build_ms").update((t_build - t0) * 1000)
    g.histogram("probe_ms").update((_time.perf_counter() - t_build) * 1000)
    return res


# ---------------------------------------------------------------------------
# materialization
# ---------------------------------------------------------------------------

def _take_nullable(col, take: np.ndarray, matched: np.ndarray):
    """col.take(take) with rows where matched is False forced NULL (the
    unmatched half of a LEFT join). Stays in whatever domain the column is
    in — code-backed columns gather codes, never strings."""
    from ..data.batch import Column

    if matched.all():
        return col.take(take)
    safe = np.where(matched, take, 0)
    out = col.take(safe)
    validity = out.valid_mask() & matched
    if out.is_code_backed:
        pool, codes = out.dict_cache
        return Column.from_codes(pool, codes, validity)
    if out._values is None:
        res = Column(validity=validity, arrow=out.arrow)
    else:
        res = Column(out._values, validity)
    res.dict_cache = out.dict_cache
    return res


def materialize_join(
    left,
    right,
    res: JoinResult,
    left_cols: Sequence[tuple[str, str]],
    right_cols: Sequence[tuple[str, str]],
):
    """Gather the joined output batch: left_cols / right_cols are
    (source column, output name) pairs. Right-side columns of a LEFT join
    carry NULL at unmatched rows. All gathers are structural Column ops —
    code-backed and arrow-backed columns never materialize objects here."""
    from ..data.batch import ColumnBatch
    from ..types import DataField, RowType

    matched = res.matched
    fields = []
    cols = {}
    for src, out in left_cols:
        fields.append((out, left.schema.field(src).type))
        cols[out] = left.column(src).take(res.left_take)
    for src, out in right_cols:
        fields.append((out, right.schema.field(src).type))
        cols[out] = _take_nullable(right.column(src), res.right_take, matched)
    schema = RowType(tuple(DataField(i, n, t) for i, (n, t) in enumerate(fields)))
    return ColumnBatch(schema, cols)


# ---------------------------------------------------------------------------
# JoinIndex: cached build side for repeated probes (lookup joins)
# ---------------------------------------------------------------------------

class JoinIndex:
    """Build once per refresh epoch, probe many times. The build side's key
    lanes encode against build-only pools, truncate/pack through the lane
    planner (no OVC — equality only), fold into <= 64-bit codes, and sort
    once. Each probe batch pays: per-key-column encode against the cached
    pool with an exact `present` mask (a probe value outside the build's
    pool or lane range is provably unmatched — masked, never a false
    match), one searchsorted, one host expansion. Keys too wide to fold
    (> 2 packed operands) keep the raw batch and delegate to join_batches
    per probe call."""

    def __init__(self, batch, key_names: Sequence[str]):
        from .lanes import lane_stats, plan_lanes_from_stats, apply_plan

        self.batch = batch
        self.key_names = list(key_names)
        self.pools: dict[str, np.ndarray] = {}
        n = batch.num_rows
        live = np.ones(n, dtype=np.bool_)
        lanes: list[np.ndarray] = []
        self._col_lanes: list[tuple[str, TypeRoot, int]] = []  # (name, root, lane count)
        from ..data.keys import _encode_column

        for name in self.key_names:
            col = batch.column(name)
            root = batch.schema.field(name).type.root
            if col.validity is not None:
                live &= col.validity
            if root in _STRING_ROOTS:
                pool = _present_string_pool([col])
                self.pools[name] = pool
                if len(pool) == 0:  # all-null build column: nothing matches
                    live &= False
                    got = [np.zeros(n, dtype=np.uint32)]
                elif _cache_full(col):
                    got = [self._ranks_cached(pool, col)]
                else:
                    got = _encode_column(_null_filled_values(col, pool), root, pool)
            else:
                got = _encode_column(col.values, root, None)
            lanes.extend(got)
            self._col_lanes.append((name, root, len(got)))
        self.lanes = (
            np.stack(lanes, axis=1).astype(np.uint32, copy=False)
            if lanes
            else np.zeros((n, 0), dtype=np.uint32)
        )
        self.live = live
        if live.any():
            self.los, self.his = lane_stats(self.lanes[live] if not live.all() else self.lanes)
        else:  # empty/all-null build: a degenerate plan no probe can match
            k = self.lanes.shape[1]
            self.los = np.zeros(k, dtype=np.uint32)
            self.his = np.zeros(k, dtype=np.uint32)
        self.plan = plan_lanes_from_stats(self.lanes.shape[1], self.los, self.his)
        packed = apply_plan(self.plan, self.lanes)
        self.wide = packed.shape[1] > 2
        if self.wide:
            return
        codes = _fold_codes(packed)
        vi = np.flatnonzero(live)
        order = np.argsort(codes[vi], kind="stable")
        self.row_of = vi[order].astype(np.int64)
        self.sorted_codes = codes[vi][order]

    @staticmethod
    def _ranks_cached(pool, col):
        from ..data.keys import _ranks_from_cache

        return _ranks_from_cache(pool, col.dict_cache)

    # ---- probe ----------------------------------------------------------
    def _probe_lanes(self, batch, keys: Sequence[str]):
        """(lanes, present): probe lanes in the build's lane space, with
        rows that provably cannot match (null key, string absent from the
        build pool, probe code pool entry absent) masked out."""
        from ..data.keys import _encode_column
        from .dicts import cache_usable, remap_codes

        n = batch.num_rows
        present = np.ones(n, dtype=np.bool_)
        lanes: list[np.ndarray] = []
        for (bname, root, cnt), pname in zip(self._col_lanes, keys):
            col = batch.column(pname)
            proot = batch.schema.field(pname).type.root
            if proot != root:
                raise JoinError(f"probe key {pname} is {proot}, index key {bname} is {root}")
            if col.validity is not None:
                present &= col.validity
            if root in _STRING_ROOTS:
                pool = self.pools[bname]
                if cache_usable(col):
                    # pool-sized compare: map the probe's pool into the build
                    # pool, flag missing entries, gather through the codes
                    ppool, codes = col.dict_cache
                    if len(pool) == 0 or len(ppool) == 0:
                        present &= False
                        lanes.append(np.zeros(n, dtype=np.uint32))
                        continue
                    idx = np.searchsorted(pool, ppool)
                    clipped = np.minimum(idx, len(pool) - 1)
                    entry_ok = pool[clipped] == ppool
                    safe_codes = np.minimum(codes, len(ppool) - 1)
                    present &= entry_ok.take(safe_codes)
                    lanes.append(remap_codes(clipped.astype(np.uint32), safe_codes))
                    continue
                values = _null_filled_values(col, pool)
                if len(pool) == 0:
                    present &= False
                    lanes.append(np.zeros(n, dtype=np.uint32))
                    continue
                ranks = np.searchsorted(pool, values)
                clipped = np.minimum(ranks, len(pool) - 1)
                present &= pool[clipped] == values
                lanes.append(clipped.astype(np.uint32))
            else:
                lanes.extend(_encode_column(col.values, root, None))
        pl = (
            np.stack(lanes, axis=1).astype(np.uint32, copy=False)
            if lanes
            else np.zeros((n, 0), dtype=np.uint32)
        )
        return pl, present

    def probe(self, batch, keys: Sequence[str] | None = None, how: str = "inner") -> JoinResult:
        """Join `batch` (probe side) against the indexed build side."""
        from .lanes import apply_plan

        keys = list(keys) if keys is not None else self.key_names
        if len(keys) != len(self._col_lanes):
            raise JoinError(f"probe key arity {len(keys)} != index arity {len(self._col_lanes)}")
        g = _metrics()
        n = batch.num_rows
        if self.wide:
            res = join_batches(batch, self.batch, keys, self.key_names, how=how)
            g.counter("index_probes").inc()
            return res
        pl, present = self._probe_lanes(batch, keys)
        # lanes the build plan dropped as constant still constrain equality;
        # kept lanes must fall inside the build's observed range or the
        # min-shift/pack would wrap — both cases are provable non-matches
        kept = set(self.plan.keep)
        for i in range(pl.shape[1]):
            lane = pl[:, i]
            if i not in kept:
                present &= lane == self.los[i]
            else:
                present &= (lane >= self.los[i]) & (lane <= self.his[i])
        clipped = np.clip(pl, self.los[None, :], self.his[None, :]) if pl.shape[1] else pl
        codes = _fold_codes(apply_plan(self.plan, clipped))
        lo = np.searchsorted(self.sorted_codes, codes, side="left")
        hi = np.searchsorted(self.sorted_codes, codes, side="right")
        counts = np.where(present, hi - lo, 0).astype(np.int64)
        lt, rt = _expand_pairs(counts, lo.astype(np.int64), self.row_of)
        if how == "left":
            miss = np.flatnonzero(counts == 0)
            lt = np.concatenate([lt, miss])
            rt = np.concatenate([rt, np.full(len(miss), -1, dtype=np.int64)])
            order = np.argsort(lt, kind="stable")
            lt, rt = lt[order], rt[order]
        g.counter("index_probes").inc()
        g.counter("rows_probed").inc(n)
        g.counter("rows_matched").inc(int((rt >= 0).sum()))
        return JoinResult(lt, rt, n, self.batch.num_rows, how=how, stats={"algorithm": "index"})


def _cache_full(col) -> bool:
    from .dicts import cache_usable

    return cache_usable(col)


def _fold_codes(packed: np.ndarray) -> np.ndarray:
    """(n, G<=2) uint32 -> (n,) uint64 codes preserving equality (and order,
    though only equality is used). G==0 folds to all-zeros: the constant key
    matched entirely through the dropped-lane present checks."""
    n, g = packed.shape
    if g == 0:
        return np.zeros(n, dtype=np.uint64)
    if g == 1:
        return packed[:, 0].astype(np.uint64)
    return (packed[:, 0].astype(np.uint64) << np.uint64(32)) | packed[:, 1].astype(np.uint64)
