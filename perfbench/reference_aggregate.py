"""The plain reference of an aggregation table (a sales rollup) kept from
several sources; its six controls; and the bytes any aggregation merge of it
must move.

Nothing here imports the program. Data comes from the seed and the
configuration alone: the key universe and each source's keys are
`reference_partial_update`'s (`key_ids`, `make_sources`: source s writes a
seeded `share` of the keys, one commit a source, in order). Every source
writes every value column, and a seeded `null_share` of each value cell is
null. The values make every shortcut visible:

- BIGINT: an input lies in [2**31, 2**32), so a key's total passes 2**32
  wherever two of its inputs are valid (most keys), and an int32 sum wraps;
  the fourth BIGINT column takes either sign. No total comes near int64's
  range.
- DOUBLE: about 1e9 in quarters, which no float32 holds; an input's value
  does not follow the commit order, so a key's maximum lies in another commit
  than its last in about half the keys.
- STRING: low cardinality (1000 and 10 values, `reference`'s vocabularies),
  a function of the key and the commit; the last writer's cell is null in a
  `null_share` of the keys, so `last_value` is not `last_non_null_value`.

Each field's function is the table's option `fields.<name>.aggregate-function`,
`last_non_null_value` where none is given (the documented default). The
answer: each key once; a `sum` the exact int64 sum of its non-null inputs, a
`max` the exact maximum of its non-null inputs, a `last_non_null_value` the
non-null value of the highest sequence number (the last commit that wrote
one); null where no input was non-null.

The comparison is `reference.compare`, unchanged, fed as
`reference_partial_update.for_compare` lays it out: each column's values with
a null written as the null fill, and `<name>.valid` as 0/1.
"""

from __future__ import annotations

import math

import numpy as np

import reference
from reference_partial_update import for_compare, key_ids, make_sources  # noqa: F401  (the op's too)

DEFAULT = "last_non_null_value"
_M64 = (1 << 64) - 1


def _family(spec: str) -> str:
    return spec.split(" ")[0]


def functions(config) -> dict:
    """name -> aggregate function of every value column."""
    options = config["options"]
    return {name: options.get(f"fields.{name}.aggregate-function", DEFAULT) for name, _ in config["schema"][1:]}


def _hash(ids: np.ndarray, seed: int, run: int, column: int) -> np.ndarray:
    """A 64-bit mix of (key, seed, commit, column), wrapping."""
    salt = np.uint64((seed * 0xD1B54A32D192ED03 + run * 0x94D049BB133111EB + column * 0x2545F4914F6CDD1D + 1) & _M64)
    x = ids.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15) + salt
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def source_rows(seed: int, ids: np.ndarray, run: int, schema, null_share: float) -> dict:
    """Source `run`'s rows for the keys `ids`: name -> (values, validity), a
    null cell's value the null fill."""
    out = {schema[0][0]: (ids, np.ones(len(ids), dtype=np.bool_))}
    seen = {"BIGINT": 0, "DOUBLE": 0, "STRING": 0}
    for position, (name, spec) in enumerate(schema[1:], start=1):
        family = _family(spec)
        j = seen[family]
        seen[family] += 1
        h = _hash(ids, seed, run, position)
        if family == "BIGINT":
            values = (h % np.uint64(1 << 31)).astype(np.int64) + (1 << 31)
            if j == 3:
                values = np.where((h >> np.uint64(40)) & np.uint64(1), -values, values)
        elif family == "DOUBLE":
            values = (1e9 + (h % np.uint64(1_000_000)).astype(np.float64)
                      + 0.25 * ((h >> np.uint64(24)) % np.uint64(4)).astype(np.float64))
        elif family == "STRING":
            vocab = reference.S1_VOCAB if j % 2 == 0 else reference.S2_VOCAB
            values = vocab[(h % np.uint64(len(vocab))).astype(np.int64)]
        else:
            raise ValueError(f"column {name}: no reference for type {spec!r}")
        valid = np.random.default_rng([seed, run, position]).random(len(ids)) >= null_share
        fill = "" if family == "STRING" else 0
        out[name] = (np.where(valid, values, np.asarray(fill, dtype=values.dtype)) if family != "STRING"
                     else np.where(valid, values, fill).astype(object), valid)
    return out


def _rollup(seed, ids, members, config, *, whole_rows=False, sum_wrap=None, max_dtype=np.float64,
            last_value=False) -> dict:
    """name -> (values, validity) over the keys any source wrote, in key
    order. The keyword arguments break one guarantee each (the controls):
    `whole_rows` takes each key's last input row whole, nulls included
    (deduplicate semantics); `sum_wrap` wraps the sums to that many bits;
    `max_dtype` compares the maxima in that precision; `last_value` keeps
    the last writer's string, null or not."""
    schema, fns = config["schema"], functions(config)
    keys = len(ids)
    out = {}
    for name, spec in schema[1:]:
        family = _family(spec)
        dtype = {"BIGINT": np.int64, "DOUBLE": np.float64, "STRING": object}[family]
        fill = "" if family == "STRING" else 0
        out[name] = (np.full(keys, fill, dtype=dtype), np.zeros(keys, dtype=np.bool_))
    exists = np.zeros(keys, dtype=np.bool_)
    for run, positions in enumerate(members):
        rows = source_rows(seed, ids[positions], run, schema, float(config["null_share"]))
        exists[positions] = True
        for name, _ in schema[1:]:
            values, valid = rows[name]
            acc, seen = out[name]
            fn = "last_value" if whole_rows else fns[name]
            if fn == "sum":
                acc[positions[valid]] += values[valid]
            elif fn == "max":
                at, v = positions[valid], values[valid].astype(max_dtype).astype(np.float64)
                acc[at] = np.where(seen[at], np.maximum(acc[at], v), v)
            elif fn == "last_non_null_value":
                take = np.ones(len(positions), dtype=np.bool_) if last_value else valid
                acc[positions[take]] = values[take]
                seen[positions[take]] = valid[take]
                continue
            elif fn == "last_value":
                acc[positions] = values
                seen[positions] = valid
                continue
            else:
                raise ValueError(f"column {name}: the reference knows sum, max, last_non_null_value, not {fn!r}")
            seen[positions[valid]] = True
    if sum_wrap:
        half = 1 << (sum_wrap - 1)
        for name, fn in fns.items():
            if fn == "sum":
                acc, seen = out[name]
                out[name] = ((acc + half) % (2 * half) - half, seen)
    out[schema[0][0]] = (ids.copy(), np.ones(keys, dtype=np.bool_))
    return {name: (values[exists], valid[exists]) for name, (values, valid) in out.items()}


def expected(seed, ids, members, config) -> dict:
    """What the configuration guarantees, for `reference.compare`."""
    return for_compare(_rollup(seed, ids, members, config), config["schema"])


# ---- the controls: each breaks one guarantee and must not be `correct` ------

def control_deduplicate(seed, ids, members, config) -> dict:
    """Deduplicate semantics: each key's last input row, whole, its nulls
    included."""
    return for_compare(_rollup(seed, ids, members, config, whole_rows=True), config["schema"])


def control_sum_int32(seed, ids, members, config) -> dict:
    """The sums accumulated in int32: they wrap past 2**31."""
    return for_compare(_rollup(seed, ids, members, config, sum_wrap=32), config["schema"])


def control_max_float32(seed, ids, members, config) -> dict:
    """The maxima taken in float32: 1e9 in quarters rounds to multiples of 64."""
    return for_compare(_rollup(seed, ids, members, config, max_dtype=np.float32), config["schema"])


def control_last_value(seed, ids, members, config) -> dict:
    """`last_value` for `last_non_null_value`: a null last writer wins."""
    return for_compare(_rollup(seed, ids, members, config, last_value=True), config["schema"])


def control_nulls_as_zeros(seed, ids, members, config) -> dict:
    """A null read as the null fill, a value: every cell valid."""
    out = expected(seed, ids, members, config)
    return {name: ((np.ones_like(v), m) if name.endswith(".valid") else (v, m)) for name, (v, m) in out.items()}


def control_stale_snapshot(seed, ids, members, config) -> dict:
    """The snapshot before the last source's commit."""
    return for_compare(_rollup(seed, ids, members[:-1], config), config["schema"])


CONTROLS = ("control_deduplicate", "control_sum_int32", "control_max_float32", "control_last_value",
            "control_nulls_as_zeros", "control_stale_snapshot")


# ---- the roofline -------------------------------------------------------------

def merge_bytes(rows_in: int, keys: int, fields: float) -> float:
    """Bytes any aggregation merge must move: per input row the 8-byte key,
    the 8-byte sequence number, the 1-byte row kind and a validity bit a
    field; per key an 8-byte result a field (a total, a maximum, or the row
    a pick took)."""
    return rows_in * (8 + 8 + 1 + math.ceil(fields / 8)) + keys * fields * 8
