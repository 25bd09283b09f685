"""The plain reference of a dedicated job's full compaction of one bucket:
what the job must leave behind, as functions over plain tuples and numpy
columns, and two controls.

Nothing here imports the program. A full compaction changes the table's
layout and nothing of its answer, so the reference has two halves. The answer:
each key once with the columns of its last writer, which is `reference.py`'s
`winners` of the runs the table was written from. The layout, over what an
observer reads off the snapshot chain and the manifests before and after (an
`Outcome`): the job said it committed; exactly one snapshot landed above the
one it started from and its kind is COMPACT; every live data file lies at the
top level; the live files' key ranges are disjoint and ascending (one sorted
run); no input file is still live; and the table the clone was taken from has
the snapshot and the files it had.
"""

from __future__ import annotations

from typing import NamedTuple

import reference

COMPACT = "COMPACT"


class Outcome(NamedTuple):
    """One operation, as plain values. `live` and `base_files` are
    [(level, min_key, max_key, row_count, file_name)] with keys as tuples;
    `snapshots` the (id, kind) of every snapshot above `start_snapshot`."""

    returned: bool
    start_snapshot: int
    snapshots: tuple
    num_levels: int
    live: tuple
    inputs: tuple  # file names live at start_snapshot
    base_snapshot: int  # the base table's latest snapshot id, read after the operation
    base_files: tuple


def faults(outcome: Outcome, base_snapshot: int, base_files) -> list[str]:
    """Why the operation is not a whole full compaction; empty where it is.
    `base_snapshot` and `base_files` are the base table's as set-up left it."""
    found = []
    if outcome.returned is not True:
        found.append("the job did not say that it committed")
    if len(outcome.snapshots) != 1:
        found.append(f"{len(outcome.snapshots)} snapshots above the starting one")
    elif outcome.snapshots[0] != (outcome.start_snapshot + 1, COMPACT):
        found.append(f"the snapshot that landed is {outcome.snapshots[0]}")
    if not outcome.live:
        found.append("no live data file")
    top = outcome.num_levels - 1
    below = [name for level, _, _, _, name in outcome.live if level != top]
    if below:
        found.append(f"{len(below)} live files below the top level {top}")
    ranges = sorted((lo, hi) for _, lo, hi, _, _ in outcome.live)
    if any(lo > hi for lo, hi in ranges) or any(b[0] <= a[1] for a, b in zip(ranges, ranges[1:])):
        found.append("key ranges of the live files overlap")
    still = set(outcome.inputs) & {name for _, _, _, _, name in outcome.live}
    if still:
        found.append(f"{len(still)} input files still live")
    if outcome.base_snapshot != base_snapshot or sorted(outcome.base_files) != sorted(base_files):
        found.append("the base table was touched")
    return found


def rows_if_whole(outcome: Outcome, base_snapshot: int, base_files) -> int:
    """Rows of the live files where the operation is whole, else 0: what the
    harness holds against the reference's row count for every operation."""
    if faults(outcome, base_snapshot, base_files):
        return 0
    return sum(rows for _, _, _, rows, _ in outcome.live)


def table_after(ids, home, winner_run, schema) -> dict:
    """The table's answer after the compaction: the answer before it."""
    return reference.winners(ids, home, winner_run, schema)


def control_first_writer(ids, home, winner_run, schema) -> dict:
    """The control: a compaction whose merge keeps each key's FIRST writer."""
    return reference.control_first_writer(ids, home, winner_run, schema)


def control_commits_nothing(outcome: Outcome, base_files) -> Outcome:
    """A second control: the job reports success and lands no snapshot, so the
    clone still holds its input files where they were."""
    return outcome._replace(snapshots=(), live=tuple(base_files))
