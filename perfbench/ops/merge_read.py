"""The `merge-read` op: plan, then read_all, of a primary-key table, with the
result whole on the host (upstream's TableReadBenchmark / TableFormatBenchmark
time the same). This file is the benchmark's only adapter to the program for
this op: set-up (the table, written through TableWrite from the seed), one
operation, the rows one operation reads, the program's counters, and the
output as plain numpy for the comparison with `perfbench/reference.py`.
"""

from __future__ import annotations

import os

import numpy as np

import reference

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


class Op:
    name = "merge-read"

    def __init__(self, config: dict, seed: int, workdir: str, spans):
        from paimon_tpu.catalog import FileSystemCatalog

        self.spans = spans
        self.config = config
        self.rows_per_op = int(config["rows"])  # records in the files the plan reads
        self.num_runs = int(config["runs"])
        runs, self.ids, self.home, self.winner_run = reference.make_runs(
            seed, self.rows_per_op, self.num_runs, float(config["rewrite_share"]))
        catalog = FileSystemCatalog(os.path.join(workdir, "warehouse"), commit_user="perfbench")
        self.table = catalog.create_table(
            config["table"], _row_type(config["schema"]), primary_keys=list(config["primary_keys"]),
            options={k: str(v) for k, v in config["options"].items()})
        for r, ids in enumerate(runs):  # one commit a run: the run number is the writer's order
            wb = self.table.new_batch_write_builder()
            w = wb.new_write()
            w.write(reference.columns(ids, np.full(len(ids), r, dtype=np.int64), config["schema"]))
            wb.new_commit().commit(w.prepare_commit())
        del runs

    def __call__(self):
        with self.spans.span("plan"):
            rb = self.table.new_read_builder()
            splits = rb.new_scan().plan()
        with self.spans.span("read"):
            return rb.new_read().read_all(splits)

    def rows_of(self, out) -> int:
        return out.num_rows

    def counters(self) -> dict:
        """The program's own metric registry, as it stands."""
        from paimon_tpu.metrics import registry

        return registry.snapshot()

    def describe(self) -> dict:
        """What the path resolved to: printed on an earlier line of each run."""
        return {"sort_engine": self.table.store.merge_executor().effective_sort_engine().value}

    def output_columns(self, out) -> dict:
        got = {}
        for name, _ in self.config["schema"]:
            c = out.column(name)
            got[name] = (np.asarray(c.values), c.valid_mask())
        return got

    def reference_columns(self) -> dict:
        return {n: (v, None) for n, v in reference.winners(
            self.ids, self.home, self.winner_run, self.config["schema"]).items()}
