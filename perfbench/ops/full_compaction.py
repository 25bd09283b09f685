"""The `full_compaction` op: what a dedicated compaction job does for one
bucket of a table whose writers run write-only:
`DedicatedCompactor(table).run_once(full=True)`, which is
`TableWrite.compact(full=True)`, `prepare_commit()` and
`TableCommit.commit_messages(...)`, one COMPACT snapshot landed. This file is
the benchmark's only adapter to the program for this op.

Set-up is `ops/merge_read.py`'s, word for word (this Op inherits it): the
configuration's table written from the seed as overlapping sorted runs, a
commit a run, `write-only` true, so the runs stay at level 0. That table is
the BASE and is never compacted. Each operation first (span `pb:clone`, the
benchmark's own overhead inside the operation) drops the clone the operation
before it made and makes a fresh clone of the base in the same warehouse:
data files by `os.link` (they are immutable), metadata (`schema/`,
`snapshot/` with its `LATEST` and `EARLIEST` hints, which the program
overwrites, `manifest/`) by copy. Then it runs the job on the clone. No pool
of clones is made beforehand, so a faster program has nothing to outrun. The
last operation's clone is kept for the comparison.

`rows_per_op` is the configuration's `rows`: input rows of the files a
compaction reads, as in the read cells. An operation's result is a
`reference_compaction.Outcome` of plain values and the clone's name; nothing
of the table is held between operations. `rows_of(result)` is the rows of
the clone's live files where the operation is a whole full compaction by
`reference_compaction.faults`, and 0 otherwise, so the harness's
`operations_with_wrong_row_count` counts operations that did not do their
work. `output_columns` reads the last clone back whole through the normal
path (plan + read_all); `reference_columns` is each key's last writer.
"""

from __future__ import annotations

import importlib.util
import os
import resource
import shutil

import reference_compaction
from reference_compaction import Outcome

HERE = os.path.dirname(os.path.abspath(__file__))


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location(f"perfbench_ops_{name}", os.path.join(HERE, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _files(table) -> tuple:
    """The live data files of the table's latest snapshot, as plain tuples."""
    splits = table.new_read_builder().new_scan().plan()
    return tuple((f.level, tuple(f.min_key), tuple(f.max_key), f.row_count, f.file_name)
                 for split in splits for f in split.files)


def _latest(table) -> int:
    return table.store.snapshot_manager.latest_snapshot_id()


class Op(_sibling("merge_read").Op):
    name = "full-compaction"

    def __init__(self, config: dict, seed: int, workdir: str, spans):
        from paimon_tpu.catalog import FileSystemCatalog

        super().__init__(config, seed, workdir, spans)  # the base, written as the read cells write it
        self.catalog = FileSystemCatalog(os.path.join(workdir, "warehouse"), commit_user="perfbench")
        self.num_levels = int(config["program_defaults"]["num-levels"])
        self.base_snapshot, self.base_files = _latest(self.table), _files(self.table)
        self.made = 0  # clones made so far; the newest is `<table>_clone_<made>`
        self.last = None  # the clone of the operation before

    def _clone(self) -> str:
        """A fresh clone of the base: data files linked, metadata copied."""
        self.made += 1
        ident = f"{self.config['table']}_clone_{self.made}"
        src, dst = self.table.path, self.catalog.table_path(ident)
        for root, _, names in os.walk(src):
            target = os.path.join(dst, os.path.relpath(root, src))
            os.makedirs(target, exist_ok=True)
            place = os.link if os.path.basename(root).startswith("bucket-") else shutil.copyfile
            for name in names:
                place(os.path.join(root, name), os.path.join(target, name))
        return ident

    def __call__(self):
        from paimon_tpu.table.compactor import DedicatedCompactor

        with self.spans.span("clone"):
            if self.last is not None:
                self.catalog.drop_table(self.last)
            self.last = ident = self._clone()
        clone = self.catalog.get_table(ident)
        start, inputs = _latest(clone), tuple(name for *_, name in _files(clone))
        returned = DedicatedCompactor(clone).run_once(full=True)
        clone = self.catalog.get_table(ident)
        manager = clone.store.snapshot_manager
        landed = tuple((i, manager.snapshot(i).commit_kind.value) for i in range(start + 1, _latest(clone) + 1))
        return ident, Outcome(returned, start, landed, self.num_levels, _files(clone), inputs,
                              _latest(self.table), _files(self.table))

    def rows_of(self, result) -> int:
        return reference_compaction.rows_if_whole(result[1], self.base_snapshot, self.base_files)

    def describe(self) -> dict:
        return {**super().describe(), "base_snapshot": self.base_snapshot, "base_files": len(self.base_files),
                "clones": self.made, "ru_maxrss_kb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss}

    def output_columns(self, result) -> dict:
        """The last clone as the next snapshot read returns it."""
        rb = self.catalog.get_table(result[0]).new_read_builder()
        return super().output_columns(rb.new_read().read_all(rb.new_scan().plan()))

    def reference_columns(self) -> dict:
        return {n: (v, None) for n, v in reference_compaction.table_after(
            self.ids, self.home, self.winner_run, self.config["schema"]).items()}
