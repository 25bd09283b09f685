"""The `ingest` op: one commit of an upsert stream into a primary-key table
with compaction on: write(batch i), prepare_commit(), commit_messages(i), as
a streaming sink does at a checkpoint. This file is the benchmark's only
adapter to the program for this op.

Set-up: run 0 (every key once) through TableWrite, compacted fully, so the
table starts as one run at the top level; then the stream writer and
committer the window uses; then every batch of the pool, keys and columns,
from the seed (`reference_ingest.ZipfStream`): the source's datagen is
another operator's work, not the sink's. No batch is sent twice; a window
that outruns the pool fails the run.

`rows_per_op` is the batch: `rows_per_s` is input rows of the writer
acknowledged by a commit per second, duplicates of a hot key included.

`correct`: the clock stopped, `output_columns` reads the whole table back
through the normal path (plan + read_all) and `reference_columns` is each key
once with the columns of its last writer over run 0 and EVERY commit this op
made, warm-up included: an acknowledged write is read back, for every
acknowledged write. `rows_of(ack)` serves `run.py`'s
`operations_with_wrong_row_count`: every key exists from run 0 on, so the
table holds `keys` rows after every commit, and an acknowledgement counts for
that many where `commit_messages` returned at least one snapshot id and every
id lies above the highest this op had seen (a commit that compacts lands two,
APPEND and COMPACT; a replayed identifier lands none), and for 0 otherwise. So
that number counts commits that were not acknowledged, or not in order.
"""

from __future__ import annotations

import json
import os

import numpy as np

import reference
import reference_ingest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TYPES = ("BIGINT", "DOUBLE", "STRING")


def _row_type(schema):
    import paimon_tpu as pt

    fields = []
    for name, spec in schema:
        base, _, rest = spec.partition(" ")
        if base not in _TYPES or rest not in ("", "NOT NULL"):
            raise ValueError(f"column {name}: type {spec!r} is not one this op can write")
        fields.append((name, getattr(pt, base)(rest != "NOT NULL")))
    return pt.RowType.of(*fields)


def _traffic_of(config_name: str) -> dict:
    """The traffic mix of the one cell that runs this op on this
    configuration (run.py hands an op its configuration alone)."""
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        cells = [w for w in json.load(f)["workloads"] if w["config"] == config_name]
    mixes = []
    for cell in cells:
        with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
            mix = json.load(f)
        if mix["op"] == "ingest":
            mixes.append(mix)
    if len(mixes) != 1:
        raise ValueError(f"{len(mixes)} ingest cells on configuration {config_name!r}: the op cannot tell its traffic")
    return mixes[0]


class Op:
    name = "ingest"

    def __init__(self, config: dict, seed: int, workdir: str, spans, traffic: dict | None = None):
        from paimon_tpu.catalog import FileSystemCatalog

        traffic = traffic or _traffic_of(config["name"])
        self.spans = spans
        self.config = config
        self.keys = int(config["rows"])
        self.rows_per_op = int(traffic["batch_rows"])
        if self.rows_per_op != int(config["commit_interval_rows"]):
            raise ValueError("the traffic's batch_rows is not the configuration's commit_interval_rows")
        self.ids = reference_ingest.key_universe(self.keys)
        self.stream = reference_ingest.ZipfStream(seed, self.keys, float(config["keys"]["exponent"]), self.rows_per_op)

        catalog = FileSystemCatalog(os.path.join(workdir, "warehouse"), commit_user="perfbench")
        self.table = catalog.create_table(
            config["table"], _row_type(config["schema"]), primary_keys=list(config["primary_keys"]),
            options={k: str(v) for k, v in config["options"].items()})
        wb = self.table.new_batch_write_builder()
        w = wb.new_write()
        w.write(reference.columns(self.ids, np.zeros(self.keys, dtype=np.int64), config["schema"]))  # run 0
        w.compact(full=True)
        wb.new_commit().commit(w.prepare_commit())
        w.close()

        builder = self.table.new_stream_write_builder()
        self.writer, self.committer = builder.new_write(), builder.new_commit()
        self.next = 1  # the next commit's identifier, and its batch's writing run
        self.highest_snapshot = self.table.store.snapshot_manager.latest_snapshot().id
        self.committed = 0  # batches 1..committed are acknowledged
        self.positions = {i: self.stream.positions(i) for i in range(1, int(traffic["pool_batches"]) + 1)}
        self.pool = {i: reference_ingest.batch_columns(self.ids, p, i, config["schema"])
                     for i, p in self.positions.items()}

    def __call__(self):
        i = self.next
        batch = self.pool.pop(i, None)
        if batch is None:
            raise RuntimeError(f"the window outran the pool: no batch {i} (traffic pool_batches)")
        with self.spans.span("write"):
            self.writer.write(batch)
        with self.spans.span("prepare_commit"):
            messages = self.writer.prepare_commit()
        with self.spans.span("commit"):
            snapshot_ids = self.committer.commit_messages(i, messages)
        ack = (tuple(snapshot_ids), self.highest_snapshot)
        self.next = i + 1
        self.committed = i
        self.highest_snapshot = max(self.highest_snapshot, *snapshot_ids) if snapshot_ids else self.highest_snapshot
        return ack

    def rows_of(self, ack) -> int:
        snapshot_ids, seen_before = ack
        return self.keys if snapshot_ids and min(snapshot_ids) > seen_before else 0

    def counters(self) -> dict:
        """The program's own metric registry, as it stands."""
        from paimon_tpu.metrics import registry

        return registry.snapshot()

    def describe(self) -> dict:
        """What the path resolved to: printed on an earlier line of each run."""
        return {"sort_engine": self.table.store.merge_executor().effective_sort_engine().value,
                "keys": self.keys, "pool_batches": len(self.pool), "commits": self.next - 1}

    def output_columns(self, last) -> dict:
        """The whole table as the next snapshot read returns it."""
        rb = self.table.new_read_builder()
        out = rb.new_read().read_all(rb.new_scan().plan())
        got = {}
        for name, _ in self.config["schema"]:
            c = out.column(name)
            got[name] = (np.asarray(c.values), c.valid_mask())
        return got

    def reference_columns(self) -> dict:
        batches = [(i, self.positions[i]) for i in range(1, self.committed + 1)]
        return {n: (v, None) for n, v in reference_ingest.table_after(self.ids, batches, self.config["schema"]).items()}
