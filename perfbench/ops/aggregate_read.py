"""The `aggregate-read` op: plan, then read_all, of a whole table kept by the
aggregation merge engine (`merge-engine: aggregation`), result whole on the
host: an analyst's batch read of a rollup that several sources add to. This
file is the benchmark's only adapter to the program for this op.

Set-up: the configuration's table, created with its options and nothing
else (the engine, the fields' `aggregate-function`s; a field without one
takes the documented default), then each source of `reference_aggregate`
written through TableWrite as one commit, in source order: the key and every
value column, a seeded share of the cells null. An operation is a fresh read
builder, `new_scan().plan()` (the benchmark's span `pb:plan`) and
`new_read().read_all(splits)` (`pb:read`), no filter. `rows_per_op` is the
table's input rows, the configuration's `rows`. The output goes to the
comparison as `reference_partial_update.for_compare` has it: each column's
values with nulls written as the null fill, and its validity.
"""

from __future__ import annotations

import importlib.util
import os
import resource

import numpy as np

import reference_aggregate as ref

HERE = os.path.dirname(os.path.abspath(__file__))


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location(f"perfbench_ops_{name}", os.path.join(HERE, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_row_type = _sibling("merge_read")._row_type


class Op:
    name = "aggregate-read"

    def __init__(self, config: dict, seed: int, workdir: str, spans):
        from paimon_tpu.catalog import FileSystemCatalog
        from paimon_tpu.data.batch import Column, ColumnBatch

        self.spans = spans
        self.config = config
        self.seed = seed
        self.schema = config["schema"]
        self.ids = ref.key_ids(int(config["keys"]))
        self.members = ref.make_sources(seed, int(config["keys"]), config["sources"])
        if sum(len(m) for m in self.members) != int(config["rows"]):
            raise ValueError(f"the sources hold {sum(len(m) for m in self.members)} rows, not {config['rows']}")
        catalog = FileSystemCatalog(os.path.join(workdir, "warehouse"), commit_user="perfbench")
        row_type = _row_type(self.schema)
        self.table = catalog.create_table(
            config["table"], row_type, primary_keys=list(config["primary_keys"]),
            options={k: str(v) for k, v in config["options"].items()})
        for run, positions in enumerate(self.members):
            rows = ref.source_rows(seed, self.ids[positions], run, self.schema, float(config["null_share"]))
            batch = ColumnBatch(row_type, {name: Column(values, valid) for name, (values, valid) in rows.items()})
            del rows
            wb = self.table.new_batch_write_builder()
            w = wb.new_write()
            w.write(batch)
            wb.new_commit().commit(w.prepare_commit())
            del batch, w
        self.rows_per_op = int(config["rows"])

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
        splits = self.table.new_read_builder().new_scan().plan()
        return {"sort_engine": self.table.store.merge_executor().effective_sort_engine().value,
                "merge_engine": self.config["options"]["merge-engine"], "splits": len(splits),
                "files_planned": sum(len(s.files) for s in splits), "rows_planned": sum(s.row_count for s in splits),
                "rows_per_op": self.rows_per_op, "ru_maxrss_kb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss}

    def output_columns(self, out) -> dict:
        rows = {}
        for name, _ in self.schema:
            c = out.column(name)
            rows[name] = (np.asarray(c.values), c.valid_mask())
        return ref.for_compare(rows, self.schema)

    def reference_columns(self) -> dict:
        return ref.expected(self.seed, self.ids, self.members, self.config)
