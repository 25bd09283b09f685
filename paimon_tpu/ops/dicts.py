"""Dictionary-domain unification: codes as the merge currency (ISSUE 10).

LSM-OPD computes directly on compressed LSM data; LUDA's GPU compactor
re-maps input dictionaries on device instead of decompressing. This module
is that move for the uint32-lane merge kernel: when every input of a merge
is dictionary-encoded, the per-file sorted pools unify into ONE pool and
each input's codes re-map through a vectorized gather — the re-mapped codes
are directly comparable (rank order == string order), so they become key
lanes, dedup/aggregation operands, and finally the dictionary page of the
output file without a string object ever materializing in between.

The pieces:

  sort_dictionary — one file dictionary (parquet insertion order) → sorted
                    pool + old-code→rank gather table
  unify_pools     — N sorted pools → one sorted pool + per-input gather
                    tables (the LUDA re-map; host object work is O(sum of
                    POOL sizes), never O(rows))
  remap_codes     — the |rows|-sized gather, numpy engine with a jittable
                    JAX twin (PAIMON_TPU_DICT_ENGINE=jax)
  unify_columns   — Column.concat's seam: concatenate code-backed columns
                    entirely in the code domain
  prune_pool      — drop pool entries no surviving code references before a
                    dictionary page is written (file dictionaries stay
                    minimal across compaction chains)
  partition_rows  — value-hash shuffle partitioner (ISSUE 20): rows hash
                    over pool VALUES gathered through their codes, so two
                    workers with disjoint code spaces agree on the shuffle
                    range of every shared group key

`merge.dict-domain` (default off) gates the reader that produces code-backed
columns; PAIMON_TPU_DICT_DOMAIN overrides in either direction (the
decoder/encoder/lanes rollout pattern). A unified domain larger than
`merge.dict-domain.pool-limit` falls back to the expanded path per merge —
codes stay uint32 and the pool stays cheap to unify.
"""

from __future__ import annotations

import os
import time
from typing import Sequence

import numpy as np

__all__ = [
    "resolve_dict_domain",
    "resolve_pool_limit",
    "sort_dictionary",
    "unify_pools",
    "remap_codes",
    "remap_codes_np",
    "remap_codes_jax",
    "unify_column_pools",
    "unify_columns",
    "prune_pool",
    "cache_usable",
    "encode_column",
    "pool_value_hashes",
    "partition_rows",
    "partition_rows_np",
    "partition_rows_jax",
]

DEFAULT_POOL_LIMIT = 1 << 20  # codes stay far inside uint32/int32 range


def resolve_dict_domain(enabled: bool | str | None) -> bool:
    """One resolution order everywhere: the PAIMON_TPU_DICT_DOMAIN env var
    (verify stages force both paths) beats the caller's option value, which
    beats the default (off)."""
    env = os.environ.get("PAIMON_TPU_DICT_DOMAIN", "").strip().lower()
    if env in ("0", "off", "false"):
        return False
    if env in ("1", "on", "true"):
        return True
    if enabled is None:
        return False
    if isinstance(enabled, str):
        return enabled.strip().lower() in ("1", "on", "true")
    return bool(enabled)


def resolve_pool_limit(limit: int | str | None) -> int:
    """PAIMON_TPU_DICT_POOL_LIMIT env beats the option value beats the
    default. The limit bounds BOTH a single file's dictionary (reader
    admission) and a unified merge domain (concat fallback)."""
    env = os.environ.get("PAIMON_TPU_DICT_POOL_LIMIT", "").strip()
    if env:
        return int(env)
    if limit is None:
        return DEFAULT_POOL_LIMIT
    return int(limit)


def _metrics():
    from ..metrics import dict_metrics

    return dict_metrics()


def sort_dictionary(dictionary: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(sorted pool, remap) for one file dictionary: pool is the sorted
    distinct value set and remap[old_code] is the value's rank in the pool.
    Parquet dictionaries are insertion-ordered and normally duplicate-free;
    np.unique tolerates duplicates (they collapse to one rank).

    String/bytes dictionaries normalize to object pools; FIXED-WIDTH
    dictionaries (int32/int64/date — ISSUE 12) keep their native dtype, so
    code-backed numeric columns expand to exactly the array the plain
    decode would have produced."""
    if len(dictionary) == 0:
        return dictionary, np.zeros(0, dtype=np.uint32)
    pool, inverse = np.unique(dictionary, return_inverse=True)
    if pool.dtype != np.dtype(object) and pool.dtype.kind not in "biufM":
        pool = pool.astype(object)
    return pool, inverse.astype(np.uint32, copy=False)


def unify_pools(
    pools: Sequence[np.ndarray],
) -> tuple[np.ndarray, list[np.ndarray | None]]:
    """Merge N sorted pools into one sorted pool; returns per-input gather
    tables mapping input ranks to unified ranks (None = identity). Object
    comparisons stay O(sum |pool|) — the rows never participate."""
    g = _metrics()
    t0 = time.perf_counter()
    first = pools[0]
    same = all(p is first for p in pools)
    if not same and all(len(p) == len(first) for p in pools):
        # equal-content pools (a fact key spanning the whole dimension, a
        # re-read of the same file set): one vectorized compare beats the
        # full unify by an order of magnitude
        try:
            same = all(bool(np.asarray(p == first).all()) for p in pools[1:])
        except (TypeError, ValueError):
            same = False
    if same:
        g.counter("pools_unified").inc(len(pools))
        g.histogram("unify_ms").update((time.perf_counter() - t0) * 1000)
        return first, [None] * len(pools)
    merged = np.concatenate([p for p in pools]) if pools else np.empty(0, dtype=object)
    if len(merged) == 0:
        unified = merged
        remaps: list[np.ndarray | None] = [np.zeros(0, dtype=np.uint32) for _ in pools]
    elif merged.dtype == np.dtype(object) and len(merged) >= 65_536:
        # large object domains: dedupe + rank through arrow's C hash table
        # (the build_string_pool move) — np.unique would object-compare-sort
        # the whole concatenation, which dominates big code-domain joins
        got = _unify_pools_arrow(pools)
        if got is None:
            unified, inverse = np.unique(merged, return_inverse=True)
            remaps = _split_inverse(inverse, pools)
        else:
            unified, remaps = got
    else:
        unified, inverse = np.unique(merged, return_inverse=True)
        # object pools stay object; fixed-width pools keep their native
        # dtype (the expansion contract of sort_dictionary)
        if unified.dtype != np.dtype(object) and merged.dtype == np.dtype(object):
            unified = unified.astype(object)
        remaps = _split_inverse(inverse, pools)
    g.counter("pools_unified").inc(len(pools))
    g.histogram("unify_ms").update((time.perf_counter() - t0) * 1000)
    return unified, remaps


def _split_inverse(inverse: np.ndarray, pools) -> list:
    inverse = inverse.astype(np.uint32, copy=False)
    remaps = []
    off = 0
    for p in pools:
        remaps.append(inverse[off : off + len(p)])
        off += len(p)
    return remaps


def _unify_pools_arrow(pools):
    """(unified sorted pool, per-input remaps) through arrow's C hash
    table: unique over all pools, one object sort of the DISTINCT set only,
    then index_in per input pool — identical output contract to the
    np.unique path, at hash speed. None = values arrow cannot hash."""
    try:
        import pyarrow as pa
        import pyarrow.compute as pc

        arrays = [pa.array(p, from_pandas=True) for p in pools]
        chunked = pa.chunked_array([a for a in arrays if len(a)])
        uniq = pc.drop_null(pc.unique(chunked)).to_numpy(zero_copy_only=False)
        if uniq.dtype != np.dtype(object):
            uniq = uniq.astype(object)
        uniq.sort()
        value_set = pa.array(uniq, from_pandas=True)
        remaps = [
            pc.index_in(a, value_set=value_set)
            .to_numpy(zero_copy_only=False)
            .astype(np.uint32)
            for a in arrays
        ]
        return uniq, remaps
    except (TypeError, ValueError, OverflowError, pa.lib.ArrowInvalid):
        return None


def remap_codes_np(remap: np.ndarray, codes: np.ndarray) -> np.ndarray:
    return remap.take(codes).astype(np.uint32, copy=False)


def remap_codes_jax(remap, codes):
    import jax.numpy as jnp

    return jnp.take(jnp.asarray(remap), jnp.asarray(codes), axis=0)


def remap_codes(remap: np.ndarray | None, codes: np.ndarray) -> np.ndarray:
    """codes → remap[codes], the |rows|-sized vectorized gather (LUDA's
    device re-map). Engine-routed like decode.kernels.gather: numpy by
    default, the JAX twin under PAIMON_TPU_DICT_ENGINE=jax."""
    codes = codes.astype(np.uint32, copy=False)
    if remap is None or len(codes) == 0:
        return codes
    _metrics().counter("codes_remapped").inc(len(codes))
    if os.environ.get("PAIMON_TPU_DICT_ENGINE") == "jax":
        return np.asarray(remap_codes_jax(remap, codes)).astype(np.uint32, copy=False)
    return remap_codes_np(remap, codes)


def cache_usable(col) -> bool:
    """True when a Column's dict_cache is a full-length (pool, codes) pair —
    the precondition every code-domain consumer checks."""
    cache = getattr(col, "dict_cache", None)
    return cache is not None and len(cache[1]) == len(col)


def unify_column_pools(cols: Sequence, limit: int | None = None):
    """(unified sorted pool, per-column remaps) of code-backed columns, or
    None when the unified domain exceeds the pool limit."""
    pools = [c.dict_cache[0] for c in cols]
    if sum(len(p) for p in pools) > resolve_pool_limit(limit) and len(set(map(id, pools))) > 1:
        # cheap upper bound first; the exact unified size needs the unify
        # itself, which we refuse to pay past the limit
        return None
    unified, remaps = unify_pools(pools)
    if len(unified) > resolve_pool_limit(limit):
        return None
    return unified, remaps


def unify_columns(cols: Sequence, validity: np.ndarray | None, limit: int | None = None):
    """Concatenate code-backed columns without leaving the code domain:
    unify their pools, re-map and concatenate their codes. Returns the
    concatenated code-backed Column, or None when the unified domain
    exceeds the pool limit (the caller falls back to expanded concat)."""
    from ..data.batch import Column

    got = unify_column_pools(cols, limit)
    if got is None:
        _metrics().counter("fallback_expanded").inc(sum(len(c) for c in cols))
        return None
    unified, remaps = got
    codes = np.concatenate(
        [remap_codes(r, c.dict_cache[1]) for r, c in zip(remaps, cols)]
    )
    return Column.from_codes(unified, codes, validity)


def encode_column(col) -> tuple[np.ndarray, np.ndarray]:
    """One Column → (sorted pool, uint32 codes) with NULL rows encoded as the
    sentinel code ``len(pool)`` — the GROUP-BY key currency (ISSUE 16).

    Code-backed columns stay in the compressed domain: their cached pool is
    pruned to the referenced entries and the cached codes re-rank without a
    value ever materializing. Expanded columns encode via np.unique over the
    valid subset (fixed-width pools keep their native dtype, strings
    normalize to object); a mixed-type object column that numpy cannot sort
    falls back to a first-seen dict walk — the pool may then be unsorted,
    which is fine for grouping (equality is all that matters) and unify_pools
    re-sorts the concatenation anyway."""
    n = len(col)
    valid = col.valid_mask()
    if cache_usable(col):
        pool, codes = col.dict_cache
        pool, codes = prune_pool(pool, codes, None if valid.all() else valid)
        codes = codes.astype(np.uint32, copy=True)
        codes[~valid] = len(pool)
        return pool, codes
    values = col.values
    live = values[valid]
    codes = np.empty(n, dtype=np.uint32)
    try:
        pool, inv = np.unique(live, return_inverse=True)
        if pool.dtype != np.dtype(object) and values.dtype == np.dtype(object):
            pool = pool.astype(object)
    except TypeError:
        seen: dict = {}
        inv = np.empty(len(live), dtype=np.uint32)
        for i, v in enumerate(live):
            inv[i] = seen.setdefault(v, len(seen))
        pool = np.empty(len(seen), dtype=object)
        for v, c in seen.items():
            pool[c] = v
    codes[valid] = inv.astype(np.uint32, copy=False)
    codes[~valid] = len(pool)
    return pool, codes


def prune_pool(
    pool: np.ndarray, codes: np.ndarray, validity: np.ndarray | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """Restrict a (pool, codes) pair to the entries actually referenced by
    valid rows: returns (pruned pool, re-mapped codes). The pruned pool is
    exactly the sorted distinct set of the column's present values — the
    same pool build_string_pool computes from expanded values — so lane
    ranks and emitted dictionary pages are identical in both domains.
    Codes at invalid slots are re-mapped through a clip (their value is
    meaningless by contract)."""
    if len(pool) == 0:
        return pool, codes.astype(np.uint32, copy=False)
    live = codes if validity is None else codes[validity]
    used = np.zeros(len(pool), dtype=np.bool_)
    used[live] = True
    if used.all():
        return pool, codes.astype(np.uint32, copy=False)
    remap = np.cumsum(used, dtype=np.int64) - 1
    remap[~used] = 0  # dead entries: clip to a harmless rank
    return pool[used], remap_codes(remap.astype(np.uint32), codes)


# ---------------------------------------------------------------------------
# value-hash shuffle partitioner (ISSUE 20): the distributed-aggregation
# exchange keys. Hashes are pure functions of VALUES — never of pool ranks,
# process ids, or PYTHONHASHSEED — so every worker routes a given group key
# to the same shuffle range despite per-worker code spaces. Cost discipline:
# one hash per POOL entry (O(|pool|) host work), then an O(rows) uint32
# gather + mix, numpy engine with a bit-identical JAX twin.
# ---------------------------------------------------------------------------
_NULL_HASH = 0x9E3779B9  # the NULL sentinel's fixed hash slot
_HASH_SEED = 2166136261  # FNV-1a offset basis
_HASH_PRIME = 16777619  # FNV-1a prime (column mixing step)


def _fmix32(xp, h):
    """murmur3's 32-bit finalizer — pure uint32 shifts/multiplies, so the
    numpy and jax twins are bit-identical by construction."""
    h = h ^ (h >> 16)
    h = h * xp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * xp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def pool_value_hashes(pool: np.ndarray) -> np.ndarray:
    """One deterministic uint32 hash per pool entry, plus a trailing slot
    for the NULL sentinel code ``len(pool)``. Object entries hash their
    utf-8 bytes (crc32 — stable across processes); fixed-width entries hash
    canonicalized 64-bit views (-0.0 folds into +0.0 and NaNs collapse to
    one pattern, mirroring np.unique's equality so unify_pools and the
    partitioner never disagree about which values are the same group)."""
    import zlib

    n = len(pool)
    out = np.empty(n + 1, dtype=np.uint32)
    out[n] = np.uint32(_NULL_HASH)
    if n == 0:
        return out
    if pool.dtype == np.dtype(object):
        for i, v in enumerate(pool):
            if isinstance(v, str):
                b = v.encode("utf-8")
            elif isinstance(v, (bytes, bytearray)):
                b = bytes(v)
            else:
                b = repr(v).encode("utf-8")
            out[i] = zlib.crc32(b) & 0xFFFFFFFF
        return out
    kind = pool.dtype.kind
    if kind == "f":
        x = pool.astype(np.float64, copy=True)
        x += 0.0  # -0.0 + 0.0 == +0.0: signed zeros hash together
        bits = x.view(np.uint64).copy()
        bits[np.isnan(x)] = np.uint64(0x7FF8000000000000)  # one NaN pattern
    elif kind in "Mm":
        bits = pool.view(np.int64).astype(np.uint64)
    elif kind == "u":
        bits = pool.astype(np.uint64)
    else:  # signed ints / bools: two's-complement 64-bit view
        bits = pool.astype(np.int64).view(np.uint64)
    lo = (bits & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (bits >> np.uint64(32)).astype(np.uint32)
    out[:n] = _fmix32(np, lo ^ _fmix32(np, hi))
    return out


def partition_rows_np(tables: Sequence[np.ndarray], codes_list, num_parts: int) -> np.ndarray:
    h = np.full(len(codes_list[0]), _HASH_SEED, dtype=np.uint32)
    for tbl, codes in zip(tables, codes_list):
        h = _fmix32(np, (h ^ tbl.take(codes.astype(np.int64, copy=False))) * np.uint32(_HASH_PRIME))
    return (h % np.uint32(num_parts)).astype(np.uint32)


def partition_rows_jax(tables, codes_list, num_parts: int):
    import jax.numpy as jnp

    h = jnp.full(len(codes_list[0]), _HASH_SEED, dtype=jnp.uint32)
    for tbl, codes in zip(tables, codes_list):
        gathered = jnp.take(jnp.asarray(tbl), jnp.asarray(codes.astype(np.int64, copy=False)), axis=0)
        h = _fmix32(jnp, (h ^ gathered) * jnp.uint32(_HASH_PRIME))
    return h % jnp.uint32(num_parts)


def partition_rows(pools: Sequence[np.ndarray], codes_list, num_parts: int) -> np.ndarray:
    """(n,) uint32 shuffle-range id per row: per-column value hashes
    (pool_value_hashes, NULL sentinel included) gather through the uint32
    codes and mix across key columns. Engine-routed like remap_codes —
    numpy by default, the JAX twin under PAIMON_TPU_DICT_ENGINE=jax; both
    are bit-identical (pure uint32 integer mixing). Collisions only skew
    range balance, never correctness: a value maps to exactly one range."""
    if not codes_list:
        return np.zeros(0, np.uint32)
    if num_parts <= 1:
        return np.zeros(len(codes_list[0]), np.uint32)
    tables = [pool_value_hashes(p) for p in pools]
    if os.environ.get("PAIMON_TPU_DICT_ENGINE") == "jax":
        return np.asarray(partition_rows_jax(tables, codes_list, num_parts)).astype(
            np.uint32, copy=False
        )
    return partition_rows_np(tables, codes_list, num_parts)
