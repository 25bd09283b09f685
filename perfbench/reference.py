"""The plain reference of the benchmark's primary-key tables, and the
comparison that decides `correct`.

Nothing here imports the program. Data comes from the seed alone: every column
of a row is a function of (key, writing run), so the reference of a merge-read
is the winning run of each key (highest sequence number, which is the latest
run that wrote the key) and those functions. Copied from chip_smoke.py
(`make_runs`, `headline_columns`, the winner check behind `check_headline`),
because later PRs may change the program and may not change the yardstick.
"""

from __future__ import annotations

import numpy as np

S1_VOCAB = np.array([f"val-{i:04d}" for i in range(1000)], dtype=object)
S2_VOCAB = np.array([f"tag-{i}" for i in range(10)], dtype=object)

# bytes any merge of these tables must move per input row: the 8-byte key, the
# 8-byte sequence number that orders writers, the 4-byte output position
MERGE_BYTES_PER_ROW = 8 + 8 + 4


def columns(ids: np.ndarray, run: np.ndarray, schema) -> dict:
    """The columns of `schema` ([(name, type)], the key first) for rows (id,
    writing run). Every column is a function of the key, and every third
    BIGINT and every other DOUBLE of the writing run too, so a stale or a
    first-written version of a row differs from the winner in several cells.
    The j-th column of a type family takes its rule from j alone, so a schema
    of any width over BIGINT, DOUBLE and STRING has its reference here.
    DOUBLEs hold quarters and halves: exact, yet outside float32's range of
    exact integers. STRINGs are of low cardinality (1000 and 10 values)."""
    out, seen = {}, {"BIGINT": 0, "DOUBLE": 0, "STRING": 0}
    for position, (name, spec) in enumerate(schema):
        family = spec.split(" ")[0]
        if position == 0:
            out[name] = ids
            continue
        j = seen[family]
        seen[family] += 1
        if family == "BIGINT":
            out[name] = (ids * 4 + run + j, ids % (97 + j), ids // (7 + j))[j % 3]
        elif family == "DOUBLE":
            out[name] = (ids.astype(np.float64) * 0.5 + run + j, ids.astype(np.float64) + 0.25 + j)[j % 2]
        elif family == "STRING":
            out[name] = S1_VOCAB[(ids + 7 * j) % 1000] if j % 2 == 0 else S2_VOCAB[(ids + j) % 10]
        else:
            raise ValueError(f"column {name}: no reference for type {spec!r}")
    return out


def make_runs(seed: int, input_rows: int, num_runs: int, rewrite_share: float):
    """num_runs sorted runs over one key universe: every key has a home run,
    and `rewrite_share` of the keys are written once more by a later run.
    Returns ([ids per run], keys ascending, home run, winning run)."""
    rng = np.random.default_rng(seed)
    distinct = int(round(input_rows / (1.0 + rewrite_share)))
    rewrites = input_rows - distinct
    home = rng.integers(0, num_runs, distinct).astype(np.int64)
    ids = np.arange(distinct, dtype=np.int64) * 3 + 1  # sparse keys: absent ones exist
    candidates = np.flatnonzero(home < num_runs - 1)
    chosen = rng.choice(candidates, size=rewrites, replace=False)
    later = home[chosen] + 1 + (rng.integers(0, 1 << 30, rewrites) % (num_runs - 1 - home[chosen]))
    runs = []
    for r in range(num_runs):
        members = np.concatenate([np.flatnonzero(home == r), chosen[later == r]])
        runs.append(np.sort(ids[members]))
    winner_run = home.copy()
    winner_run[chosen] = later
    return runs, ids, home, winner_run


def winners(ids, home, winner_run, schema):
    """What the configuration guarantees: each key once, from its last writer."""
    return columns(ids, winner_run, schema)


def control_first_writer(ids, home, winner_run, schema):
    """The control: the reference with one guarantee broken. The merge keeps
    the LOWEST sequence number of a key (first writer wins) where the
    deduplicate engine must keep the highest."""
    return columns(ids, home, schema)


def control_stale_snapshot(ids, home, winner_run, num_runs, schema):
    """A second control: the read serves the snapshot before the last commit,
    so the last run's rows are missing or older versions stand in for them."""
    last = num_runs - 1
    keep = home < last
    run = np.where(winner_run == last, home, winner_run)
    return columns(ids[keep], run[keep], schema)


def compare(got: dict, want: dict) -> list[tuple[str, int, int]]:
    """[(name, number, limit)] of one output against the reference. `got` and
    `want` map column name -> (values, validity mask or None). Rows are
    matched by key, so the order in which buckets are returned is free; a key
    returned twice, left out or invented counts once each. Every comparison
    is exact: the limit is 0. The key is the first column of `want`."""
    KEY = next(iter(want))
    got_ids = np.asarray(got[KEY][0])
    want_ids = np.asarray(want[KEY][0])
    if len(got_ids) > 1 and not bool(np.all(got_ids[1:] > got_ids[:-1])):
        order = np.argsort(got_ids, kind="stable")
        got = {n: (np.asarray(v)[order], None if m is None else np.asarray(m)[order]) for n, (v, m) in got.items()}
        got_ids = got[KEY][0]
    duplicated = int(np.count_nonzero(got_ids[1:] == got_ids[:-1])) if len(got_ids) > 1 else 0
    uniq = np.unique(got_ids) if duplicated else got_ids
    common = np.intersect1d(uniq, want_ids, assume_unique=True)
    missing = len(want_ids) - len(common)
    invented = len(uniq) - len(common)
    nulls = 0
    wrong = 0
    missing_columns = 0
    aligned = duplicated == 0 and missing == 0 and invented == 0
    if not aligned:
        gi = np.searchsorted(got_ids, common, side="left")
        wi = np.searchsorted(want_ids, common, side="left")
    for name, (w, _) in want.items():
        if name not in got:
            missing_columns += 1
            continue
        g, mask = got[name]
        g, w = np.asarray(g), np.asarray(w)
        if not aligned:
            g, w = g[gi], w[wi]
            mask = None if mask is None else np.asarray(mask)[gi]
        if mask is not None:
            nulls += int(len(mask) - np.count_nonzero(mask))
        if name != KEY:
            if g.dtype != object and w.dtype != object and g.dtype != w.dtype:
                wrong += len(w)  # a column served in another type is not the column
            else:
                wrong += int(np.count_nonzero(g != w))
    return [
        ("rows_out_minus_reference", abs(len(got_ids) - len(want_ids)), 0),
        ("keys_missing", missing, 0),
        ("keys_invented", invented, 0),
        ("keys_duplicated", duplicated, 0),
        ("columns_missing", missing_columns, 0),
        ("null_cells", nulls, 0),
        ("wrong_cells", wrong, 0),
    ]
