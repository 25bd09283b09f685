"""Normalized binary sort keys as uint32 lanes.

The reference JIT-generates per-schema comparators over BinaryRow bytes
(paimon-codegen SortCodeGenerator / NormalizedKeyComputer; loaded via
/root/reference/paimon-common/.../codegen/CompileUtils.java). The TPU analog:
encode each key column into one or two uint32 "lanes" such that unsigned
lexicographic comparison of the lane tuple equals the typed comparison of the
key tuple. Sorting N rows by a K-column key then becomes one
`jax.lax.sort(lanes..., num_keys=L)` — no comparators, no codegen, and the
same encoding serves the merge kernel, min/max stats, and range partitioning.

uint32 (not uint64) because 32-bit is the TPU's native integer width.

Encodings (all order-preserving into unsigned space):
  * signed ints  : flip the sign bit (x ^ 0x80..0), widened to 32 bits
  * floats       : IEEE total order — if sign bit set, flip all bits, else
                   set the sign bit
  * bool/date/time/timestamp/decimal(unscaled) : via the int paths
  * string/bytes : dictionary rank against a sorted pool built over all
                   inputs participating in one merge (exact, collision-free;
                   see build_string_pool). Variable-length data itself never
                   reaches the device.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Mapping, Sequence

import numpy as np

from ..types import RowType, TypeRoot
from .batch import ColumnBatch

__all__ = [
    "NormalizedKeys",
    "encode_key_lanes",
    "lane_count",
    "integer_key_columns",
    "build_string_pool",
    "exact_string_pool",
    "split_int64_lanes",
    "lexsort_rows",
]


def lane_count(row_type: RowType, key_names: Sequence[str]) -> int:
    n = 0
    for name in key_names:
        n += _lanes_for(row_type.field(name).type.root)
    return n


def _lanes_for(root: TypeRoot) -> int:
    if root in (
        TypeRoot.BOOLEAN,
        TypeRoot.TINYINT,
        TypeRoot.SMALLINT,
        TypeRoot.INT,
        TypeRoot.DATE,
        TypeRoot.TIME,
        TypeRoot.FLOAT,
        TypeRoot.CHAR,
        TypeRoot.VARCHAR,
        TypeRoot.BINARY,
        TypeRoot.VARBINARY,
    ):
        return 1
    if root in (
        TypeRoot.BIGINT,
        TypeRoot.TIMESTAMP,
        TypeRoot.TIMESTAMP_LTZ,
        TypeRoot.DOUBLE,
        TypeRoot.DECIMAL,
    ):
        return 2
    raise ValueError(f"type {root} not supported as a key column")


_INT32_ROOTS = (TypeRoot.TINYINT, TypeRoot.SMALLINT, TypeRoot.INT, TypeRoot.DATE, TypeRoot.TIME)
_INT64_ROOTS = (TypeRoot.BIGINT, TypeRoot.TIMESTAMP, TypeRoot.TIMESTAMP_LTZ, TypeRoot.DECIMAL)


def integer_key_columns(batch: ColumnBatch, key_names: Sequence[str]) -> list[np.ndarray] | None:
    """The key columns' own value arrays where EVERY key column is a plain
    fixed-width signed integer column: an int8/16/32 array for the one-lane
    roots, an int64 array for the two-lane roots, all present, values in
    hand. ops.lanes.compress_key_columns then plans and packs the sort
    operands straight from them (the dtype says how many lanes a column
    is), and the (n, K) lane matrix of encode_key_lanes is never built.
    None for anything else (a string, bytes, BOOLEAN, FLOAT or DOUBLE key, a
    code-backed column, a null, an array of another dtype than its type's):
    the caller encodes the matrix, which also raises what there is to
    raise."""
    out = []
    for name in key_names:
        root = batch.schema.field(name).type.root
        if root not in _INT32_ROOTS and root not in _INT64_ROOTS:
            return None
        col = batch.column(name)
        if col.is_code_backed or col.validity is not None:
            return None
        values = col.values
        if values.dtype.kind != "i" or (values.dtype.itemsize == 8) != (root in _INT64_ROOTS):
            return None
        out.append(values)
    return out


def split_int64_lanes(v: np.ndarray, signed: bool = True) -> tuple[np.ndarray, np.ndarray]:
    """int64 -> (hi, lo) uint32 lanes, order preserving."""
    u = v.astype(np.int64).view(np.uint64)
    if signed:
        u = u ^ np.uint64(1 << 63)
    hi = (u >> np.uint64(32)).astype(np.uint32)
    lo = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return hi, lo


def _encode_column(values: np.ndarray, root: TypeRoot, pool: np.ndarray | None) -> list[np.ndarray]:
    if root == TypeRoot.BOOLEAN:
        return [values.astype(np.uint32)]
    if root in _INT32_ROOTS:
        v32 = values.astype(np.int32)
        return [v32.view(np.uint32) ^ np.uint32(0x80000000)]
    if root in _INT64_ROOTS:
        hi, lo = split_int64_lanes(values)
        return [hi, lo]
    if root == TypeRoot.FLOAT:
        b = values.astype(np.float32).view(np.uint32)
        neg = (b & np.uint32(0x80000000)) != 0
        return [np.where(neg, ~b, b | np.uint32(0x80000000))]
    if root == TypeRoot.DOUBLE:
        b = values.astype(np.float64).view(np.uint64)
        neg = (b & np.uint64(1 << 63)) != 0
        u = np.where(neg, ~b, b | np.uint64(1 << 63))
        return [(u >> np.uint64(32)).astype(np.uint32), (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)]
    if root in (TypeRoot.CHAR, TypeRoot.VARCHAR, TypeRoot.BINARY, TypeRoot.VARBINARY):
        if pool is None:
            raise ValueError("string key column requires a pool (build_string_pool)")
        if len(pool) == 0:
            raise ValueError("string key value(s) missing from pool; pool must cover all merge inputs")
        if len(values) >= 65_536:
            ranks = _hash_ranks(values, pool)
            if ranks is not None:
                return [ranks]
        ranks = np.searchsorted(pool, values)
        # a value missing from the pool would silently collide with its
        # successor's rank — turn that data corruption into an error
        clipped = np.minimum(ranks, len(pool) - 1)
        if not bool(np.all(pool[clipped] == values)):
            raise ValueError("string key value(s) missing from pool; pool must cover all merge inputs")
        return [ranks.astype(np.uint32)]
    raise ValueError(f"type {root} not supported as key column")


def _hash_ranks(values: np.ndarray, pool: np.ndarray) -> np.ndarray | None:
    """Rank lookup through arrow's C hash table — replaces a |values| × log
    |pool| object-compare searchsorted for large merges. index_in against
    the sorted pool returns the rank directly; a null (value outside the
    pool) is the same data-corruption case the searchsorted path raises
    for. Returns None when the values cannot take the arrow path (mixed
    types) so the caller falls back."""
    try:
        import pyarrow as pa
        import pyarrow.compute as pc

        idx = pc.index_in(pa.array(values, from_pandas=True), value_set=pa.array(pool))
    except (TypeError, ValueError, OverflowError, pa.lib.ArrowInvalid):
        return None
    if idx.null_count:
        raise ValueError("string key value(s) missing from pool; pool must cover all merge inputs")
    return idx.to_numpy(zero_copy_only=False).astype(np.uint32)


def build_string_pool(column_values: Sequence[np.ndarray]) -> np.ndarray:
    """Sorted unique values across every input of one merge. Ranks against this
    pool are exact order-preserving surrogates for the strings themselves.

    Large inputs dedupe through arrow's C hash table first (object-compare
    sorting then touches only the distinct set — for dictionary-shaped key
    columns that is orders of magnitude smaller); the output contract is
    identical to np.unique: a sorted object ndarray."""
    non_empty = [v for v in column_values if len(v)]
    if not non_empty:
        return np.empty(0, dtype=object)
    total = sum(len(v) for v in non_empty)
    if total >= 65_536:
        try:
            import pyarrow as pa
            import pyarrow.compute as pc

            chunked = pa.chunked_array([pa.array(v, from_pandas=True) for v in non_empty])
            uniq = pc.drop_null(pc.unique(chunked)).to_numpy(zero_copy_only=False)
            if uniq.dtype != np.dtype(object):
                uniq = uniq.astype(object)
            uniq.sort()
            return uniq
        except (TypeError, ValueError, OverflowError, pa.lib.ArrowInvalid):
            pass  # mixed/unhashable values: the numpy sort path below
    return np.unique(np.concatenate(non_empty))


def exact_string_pool(cols: Sequence) -> np.ndarray:
    """Sorted distinct PRESENT values across the given Columns — identical
    to build_string_pool over their expanded values, but computed entirely
    in the code domain when every column carries a usable dict_cache: each
    (pool, codes) pair prunes to its referenced entries and the pruned
    pools unify (object work at |pool| scale). Falls back to the expanded
    build when any column lacks a cache."""
    from ..ops.dicts import cache_usable, prune_pool, unify_pools

    cols = list(cols)
    if cols and all(cache_usable(c) for c in cols):
        pruned = []
        for c in cols:
            pool, codes = c.dict_cache
            p, _ = prune_pool(pool, codes, c.validity)
            pruned.append(p)
        unified, _ = unify_pools(pruned)
        return unified
    return build_string_pool([c.values for c in cols])


def _ranks_from_cache(pool: np.ndarray, cache: tuple) -> np.ndarray:
    """Ranks of a cached (pool, codes) column against a caller-supplied
    sorted pool: the |pool_c|-sized searchsorted replaces the |rows|-sized
    one — the rows themselves only pay a uint32 gather (ops.dicts). A used
    code whose value is missing from the pool is the same data-corruption
    case the expanded path raises for."""
    from ..ops.dicts import remap_codes

    pool_c, codes = cache
    if pool_c is pool:
        return codes.astype(np.uint32, copy=False)
    if len(pool) == 0 or len(pool_c) == 0:
        if len(codes) == 0:
            return codes.astype(np.uint32, copy=False)
        raise ValueError("string key value(s) missing from pool; pool must cover all merge inputs")
    idx = np.searchsorted(pool, pool_c)
    clipped = np.minimum(idx, len(pool) - 1)
    entry_ok = pool[clipped] == pool_c
    ranks = remap_codes(clipped.astype(np.uint32), codes)
    if len(codes) and not bool(entry_ok.take(codes).all()):
        raise ValueError("string key value(s) missing from pool; pool must cover all merge inputs")
    return ranks


def encode_key_lanes(
    batch: ColumnBatch,
    key_names: Sequence[str],
    string_pools: Mapping[str, np.ndarray] | None = None,
) -> np.ndarray:
    """(N, L) uint32 lanes for the given key columns. Key columns must be
    non-null (primary keys are NOT NULL by schema validation).

    Side effect: string/bytes key columns get the (pool, ranks) pair cached
    on the Column (`dict_cache`) — the ranks double as exact dictionary
    codes, which the native parquet encoder consumes directly so flushed
    merge output never rematerializes key strings (any consistent pair is
    correct, so concurrent merges over a shared cached column are safe)."""
    lanes: list[np.ndarray] = []
    string_roots = (TypeRoot.CHAR, TypeRoot.VARCHAR, TypeRoot.BINARY, TypeRoot.VARBINARY)
    for name in key_names:
        col = batch.column(name)
        if col.null_count:
            raise ValueError(f"key column {name!r} contains nulls")
        root = batch.schema.field(name).type.root
        pool = None if string_pools is None else string_pools.get(name)
        cache = col.dict_cache
        if (
            root in string_roots
            and pool is not None
            and cache is not None
            and len(cache[1]) == len(col)
        ):
            # compressed-domain short circuit: the column already carries
            # dictionary codes — ranks come from a pool-sized remap + one
            # uint32 gather, zero searchsorted over the rows and zero
            # string-object comparisons
            col_lanes = [_ranks_from_cache(pool, cache)]
        elif root not in string_roots and col.is_code_backed:
            # fixed-width code domain (ISSUE 12): encode the POOL once
            # (O(|pool|)) and gather each lane through the codes — element-
            # wise encoding commutes with the gather, so the lanes are
            # numerically identical to encoding the expanded values
            cpool, codes = col.dict_cache
            col_lanes = [pl.take(codes) for pl in _encode_column(cpool, root, None)]
        else:
            col_lanes = _encode_column(col.values, root, pool)
        if pool is not None and root in string_roots:
            col.dict_cache = (pool, col_lanes[0].astype(np.uint32, copy=False))
        lanes.extend(col_lanes)
    if not lanes:
        return np.zeros((batch.num_rows, 0), dtype=np.uint32)
    return np.stack(lanes, axis=1)


@dataclass
class NormalizedKeys:
    """Lanes plus the metadata needed to interpret them."""

    lanes: np.ndarray  # (N, L) uint32
    key_names: tuple[str, ...]

    def __len__(self) -> int:
        return self.lanes.shape[0]

    @property
    def num_lanes(self) -> int:
        return self.lanes.shape[1]


def lexsort_rows(lanes: np.ndarray, *tiebreakers: np.ndarray) -> np.ndarray:
    """Host-side (numpy) stable lexicographic argsort: lanes left-to-right are
    most-to-least significant, then tiebreaker arrays. Reference oracle for the
    device kernel in paimon_tpu.ops.merge."""
    keys = list(tiebreakers)[::-1] + [lanes[:, i] for i in range(lanes.shape[1] - 1, -1, -1)]
    if not keys:
        return np.arange(lanes.shape[0])
    return np.lexsort(keys)


def encode_key_lanes_with_pools(batch, key_names):
    """encode_key_lanes with string pools auto-built for string/bytes keys —
    the idiom every key-encoding call site needs. Pools prefer the code
    domain (exact_string_pool): a column the reader delivered as dictionary
    codes never expands to build its pool."""
    from ..types import TypeRoot

    pools = {
        name: exact_string_pool([batch.column(name)])
        for name in key_names
        if batch.schema.field(name).type.root
        in (TypeRoot.CHAR, TypeRoot.VARCHAR, TypeRoot.BINARY, TypeRoot.VARBINARY)
    }
    return encode_key_lanes(batch, key_names, pools)
