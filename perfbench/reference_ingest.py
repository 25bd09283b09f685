"""The plain reference of an upsert stream into a primary-key table: the
batches from the seed, each key's last writer, and two controls.

Nothing here imports the program. The key universe is `reference.py`'s
(sparse keys 3*i+1, ascending). Run 0 writes every key once; batch i (i >= 1)
is writing run i and draws its keys from a Zipf distribution over the
universe: rank r has weight r**-exponent, ranks are scattered over the key
space by a permutation from the seed (hot keys are not neighbours), and a
draw is the inverse of the cumulative weights at a uniform number (numpy's
`zipf` needs an exponent above 1). A row's columns are
`reference.columns(id, writing run, schema)`, so duplicates of a key inside
one batch are the same row, and the table after any number of commits is each
key once with the columns of the last run that wrote it.
"""

from __future__ import annotations

import numpy as np

import reference


def key_universe(keys: int) -> np.ndarray:
    """The table's keys, ascending: sparse, so absent keys exist."""
    return np.arange(keys, dtype=np.int64) * 3 + 1


class ZipfStream:
    """Batch i's key positions (indices into `key_universe`), for any i >= 1,
    from (seed, i) alone: the same seed gives the same batches in any order."""

    def __init__(self, seed: int, keys: int, exponent: float, batch_rows: int):
        self.seed, self.keys, self.batch_rows = int(seed), int(keys), int(batch_rows)
        weights = np.arange(1, self.keys + 1, dtype=np.float64) ** -float(exponent)
        self.cdf = np.cumsum(weights)
        self.cdf /= self.cdf[-1]
        self.rank_to_key = np.random.default_rng([self.seed, 0]).permutation(self.keys).astype(np.int32)

    def positions(self, i: int) -> np.ndarray:
        if i < 1:
            raise ValueError("run 0 is the base load: every key once")
        u = np.random.default_rng([self.seed, i]).random(self.batch_rows)
        ranks = np.minimum(np.searchsorted(self.cdf, u, side="left"), self.keys - 1)
        return self.rank_to_key[ranks]


def batch_columns(ids: np.ndarray, positions: np.ndarray, i: int, schema) -> dict:
    """The rows of batch i (writing run i) in arrival order, as columns."""
    rows = ids[positions]
    return reference.columns(rows, np.full(len(rows), i, dtype=np.int64), schema)


def last_writer(keys: int, batches) -> np.ndarray:
    """The run that wrote each key last: run 0, overwritten by every batch
    (run number, positions) in commit order."""
    run = np.zeros(keys, dtype=np.int64)
    for i, positions in batches:
        run[positions] = i
    return run


def table_after(ids: np.ndarray, batches, schema) -> dict:
    """What the configuration guarantees after the commits of `batches`:
    each key once, every column from its last acknowledged writer."""
    return reference.columns(ids, last_writer(len(ids), batches), schema)


def control_last_commit_lost(ids: np.ndarray, batches, schema) -> dict:
    """The control: the last acknowledged commit is not in the table (the
    reference without the newest run)."""
    return table_after(ids, list(batches)[:-1], schema)


def control_first_writer(ids: np.ndarray, batches, schema) -> dict:
    """A second control: the merge keeps the LOWEST sequence number of a key.
    Every key exists from run 0 on, so its first writer is run 0."""
    return reference.columns(ids, np.zeros(len(ids), dtype=np.int64), schema)
