"""LSM compaction: universal strategy, upgrade-vs-rewrite tasks, rewriter.

Parity: /root/reference/paimon-core/.../mergetree/compact/ —
  UniversalCompaction.java:42 (RocksDB-style: size-amplification trigger
  pickForSizeAmp:114, size-ratio pickForSizeRatio:150, run-count trigger
  pick:100-108, optional full-compact interval :73-80),
  MergeTreeCompactManager.java:67 (triggerCompaction:115-176, dropDelete rule
  :148-158), MergeTreeCompactTask.java:40 (doCompact:77-105 partitions the
  unit into sections, *upgrades* large non-overlapping files vs *rewrites*
  overlapping/small ones), MergeTreeCompactRewriter.java:76-84 (rewrite =
  the same merge kernel as the read path + rolling writer at outputLevel).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Sequence

from ..metrics import compaction_metrics, span
from ..options import CoreOptions
from ..utils import now_millis
from .datafile import DataFileMeta, KeyValueFileReaderFactory, KeyValueFileWriterFactory
from .kv import KVBatch
from .levels import IntervalPartition, Levels, SortedRun
from .mergefn import MergeExecutor

__all__ = ["CompactUnit", "CompactResult", "UniversalCompaction", "MergeTreeCompactRewriter", "MergeTreeCompactManager"]


@dataclass
class CompactUnit:
    output_level: int
    files: list[DataFileMeta]
    file_num_based: bool = False


@dataclass
class CompactResult:
    before: list[DataFileMeta] = field(default_factory=list)
    after: list[DataFileMeta] = field(default_factory=list)
    changelog: list[DataFileMeta] = field(default_factory=list)

    def is_empty(self) -> bool:
        return not self.before and not self.after


class UniversalCompaction:
    """Pick which sorted runs to compact (reference UniversalCompaction)."""

    def __init__(
        self,
        max_size_amp_percent: int = 200,
        size_ratio_percent: int = 1,
        num_run_compaction_trigger: int = 5,
        optimization_interval_millis: int | None = None,
        max_file_num: int = 50,
    ):
        self.max_size_amp = max_size_amp_percent
        self.size_ratio = size_ratio_percent
        self.num_run_trigger = num_run_compaction_trigger
        self.opt_interval = optimization_interval_millis
        # bounds ONE size-ratio pick's input file count so a single
        # compaction cannot balloon (reference compaction.max.file-num)
        self.max_file_num = max_file_num
        self._last_opt_millis = now_millis()

    def pick(self, num_levels: int, runs: list[tuple[int, SortedRun]]) -> CompactUnit | None:
        max_level = num_levels - 1
        if self.opt_interval is not None and now_millis() - self._last_opt_millis >= self.opt_interval:
            self._last_opt_millis = now_millis()
            return self._unit(runs, max_level, len(runs))
        # 1. size amplification
        unit = self._pick_size_amp(max_level, runs)
        if unit is not None:
            return unit
        # 2. size ratio
        unit = self._pick_size_ratio(max_level, runs)
        if unit is not None:
            return unit
        # 3. run count
        if len(runs) > self.num_run_trigger:
            candidate = len(runs) - self.num_run_trigger + 1
            return self._unit(runs, max_level, candidate, file_num_based=True)
        return None

    def _pick_size_amp(self, max_level: int, runs) -> CompactUnit | None:
        if len(runs) <= self.num_run_trigger:
            return None
        candidate = sum(r.total_size() for _, r in runs[:-1])
        earliest = runs[-1][1].total_size()
        if earliest and candidate * 100 / earliest >= self.max_size_amp:
            return self._unit(runs, max_level, len(runs))
        return None

    def _pick_size_ratio(self, max_level: int, runs) -> CompactUnit | None:
        if len(runs) <= self.num_run_trigger:
            return None
        candidate_size = runs[0][1].total_size()
        count = 1
        files = len(runs[0][1].files)
        for lv, run in runs[1:]:
            if candidate_size * (100.0 + self.size_ratio) / 100.0 < run.total_size():
                break
            if files + len(run.files) > self.max_file_num:
                break
            candidate_size += run.total_size()
            files += len(run.files)
            count += 1
        if count > 1:
            return self._unit(runs, max_level, count)
        return None

    @staticmethod
    def _unit(runs, max_level: int, count: int, file_num_based: bool = False) -> CompactUnit:
        """Choose the output level for the first `count` runs (reference
        UniversalCompaction.createUnit:179-205). The tentative output is one
        level below the first excluded run; when that floor is level 0 the
        unit is extended through the remaining level-0 runs AND the first
        non-zero-level run (else its level would end up holding two runs,
        breaking the one-run-per-level invariant), outputting at that run's
        level — or max_level when everything got absorbed."""
        if count < len(runs):
            output = runs[count][0] - 1
            if output <= 0:
                while count < len(runs):
                    level = runs[count][0]
                    count += 1
                    if level != 0:
                        output = level
                        break
        if count == len(runs):
            output = max_level
        files = [f for _, r in runs[:count] for f in r.files]
        return CompactUnit(output, files, file_num_based)

    def force_full(self, num_levels: int, runs) -> CompactUnit | None:
        return self._unit(runs, num_levels - 1, len(runs)) if runs else None


class MergeTreeCompactRewriter:
    """Merge-read the unit's sections and rewrite at the output level —
    the same kernel as the read path."""

    def __init__(
        self,
        reader_factory: KeyValueFileReaderFactory,
        writer_factory: KeyValueFileWriterFactory,
        merge_executor: MergeExecutor,
        deletion_vectors: dict | None = None,
        emit_full_changelog: bool = False,
        row_deduplicate: bool = True,
        expire_predicate=None,
    ):
        self.reader_factory = reader_factory
        self.writer_factory = writer_factory
        self.merge = merge_executor
        # record-level TTL: expired rows are physically dropped on rewrite
        self.expire_predicate = expire_predicate
        # DV'd rows must be dropped during the rewrite (the commit purges the
        # dead files' DVs afterwards) — else compaction resurrects them
        self.deletion_vectors = deletion_vectors or {}
        # full-compaction changelog producer (reference
        # FullChangelogMergeTreeCompactRewriter:43)
        self.emit_full_changelog = emit_full_changelog
        self.row_deduplicate = row_deduplicate

    def _read(self, f: DataFileMeta) -> KVBatch:
        kv = self.reader_factory.read(f)
        dv = self.deletion_vectors.get(f.file_name)
        if dv is not None:
            mask = ~dv.deleted_mask(kv.num_rows)
            if not mask.all():
                kv = kv.filter(mask)
        if self.expire_predicate is not None and kv.num_rows:
            keep = self.expire_predicate.eval(kv.data)
            if not keep.all():
                kv = kv.filter(keep)
        return kv

    def rewrite(
        self, sections: list[list[SortedRun]], output_level: int, drop_delete: bool
    ) -> tuple[list[DataFileMeta], list[DataFileMeta]]:
        """Returns (new files, changelog files)."""
        return self.rewrite_complete(self.rewrite_dispatch(sections, output_level), output_level, drop_delete)

    def rewrite_pipelined(
        self,
        sections: list[list[SortedRun]],
        output_level: int,
        drop_delete: bool,
        depth: int,
        parallelism: int | None = None,
    ) -> tuple[list[DataFileMeta], list[DataFileMeta]]:
        """Pipelined rewrite: section i+1's file reads run on pipeline
        workers while section i's merge executes on device, and section i's
        output encode overlaps the dispatch of section i+1's merge (the
        resolve-previous-after-dispatch-next stagger below). Output lists are
        in section order — identical to rewrite() (the sequential path reads
        EVERY section before the first merge; this one keeps at most depth+1
        sections' inputs alive)."""
        from ..parallel.pipeline import SplitPipeline

        out: list[DataFileMeta] = []
        changelog: list[DataFileMeta] = []
        pipe = SplitPipeline(parallelism, depth, stage="compact")
        read_section = lambda section: self._read_section(section, output_level)
        pending = None  # previous section's (merge handle, old_top)
        for kv, old_top, seq_ascending in pipe.map_ordered(sections, read_section):
            handle = self.merge.merge_async(kv, seq_ascending=seq_ascending)
            if pending is not None:
                self._write_section(pending, output_level, drop_delete, out, changelog)
            pending = (handle, old_top)
        if pending is not None:
            self._write_section(pending, output_level, drop_delete, out, changelog)
        return out, changelog

    def _write_section(self, job, output_level: int, drop_delete: bool, out, changelog) -> None:
        """Resolve one section's merge and encode its output (the shared tail
        of rewrite_complete and rewrite_pipelined)."""
        handle, old_top = job
        merged = self.merge.merge_resolve(handle)
        if drop_delete:
            merged = merged.drop_deletes()
        if self.emit_full_changelog and drop_delete:
            cl = self._section_changelog(old_top, merged)
            if cl.num_rows:
                changelog.extend(
                    self.writer_factory.write(cl, level=0, file_source="compact", prefix="changelog")
                )
        out.extend(self.writer_factory.write(merged, output_level, file_source="compact"))

    def _read_section(self, section: list[SortedRun], output_level: int):
        """Read one section's runs in merge order: (concatenated KVBatch,
        old top-level batches for the changelog diff, seq_ascending) — the
        shared read head of every rewrite mode."""
        from ..parallel.pipeline import bounded_map
        from .read import order_runs_for_merge

        runs, seq_ascending = order_runs_for_merge(section)
        files = [f for run in runs for f in run.files]
        with span("compact.read", files=len(files)) as sp:
            # per-file reads fan out over the shared pool (order preserved, so
            # the concatenated runs — and the merge — are bit-identical to the
            # old serial loop); this is leaf work per the pool contract
            batches = bounded_map(self._read, files)
            decoded = sum(b.byte_size() for b in batches)
            kv = KVBatch.concat(batches)
            sp.add(rows=kv.num_rows, bytes=decoded)
        compaction_metrics().counter("bytes_in").inc(decoded)
        old_top = [b for f, b in zip(files, batches) if f.level == output_level]
        return kv, old_top, seq_ascending

    def rewrite_dispatch(self, sections: list[list[SortedRun]], output_level: int):
        """Phase 1: read every section's runs and dispatch their merges.
        Under a mesh context the merges of ALL sections (and all buckets
        whose compactions dispatched in the same batch window) execute in
        batched shard_map calls over the mesh, and the section reads stream
        through the SplitPipeline feeder (one prefetch lane per device)
        instead of running serially."""
        import threading

        from ..parallel.mesh_exec import current_mesh_context
        from ..parallel.pipeline import PIPELINE_THREAD_PREFIX

        ctx = current_mesh_context()
        # no feeder-in-feeder: when this dispatch already runs on a pipeline
        # worker (table/write.compact fans buckets out), the serial loop below
        # still fans its file reads over the shared pool
        in_worker = threading.current_thread().name.startswith(PIPELINE_THREAD_PREFIX)
        if ctx is not None and len(sections) > 1 and not in_worker:
            from ..parallel.pipeline import SplitPipeline

            lanes = ctx.feeder_lanes
            pipe = SplitPipeline(parallelism=lanes, depth=lanes, stage="compact")
            return [
                (self.merge.merge_async(kv, seq_ascending=sa), old_top)
                for kv, old_top, sa in pipe.map_ordered(
                    sections, lambda s: self._read_section(s, output_level)
                )
            ]
        jobs = []
        for section in sections:
            kv, old_top, seq_ascending = self._read_section(section, output_level)
            jobs.append((self.merge.merge_async(kv, seq_ascending=seq_ascending), old_top))
        return jobs

    def rewrite_complete(
        self, jobs, output_level: int, drop_delete: bool
    ) -> tuple[list[DataFileMeta], list[DataFileMeta]]:
        """Phase 2: resolve merges, emit changelog, write output files."""
        out: list[DataFileMeta] = []
        changelog: list[DataFileMeta] = []
        for job in jobs:
            self._write_section(job, output_level, drop_delete, out, changelog)
        return out, changelog

    def _section_changelog(self, old_top: list[KVBatch], merged: KVBatch) -> KVBatch:
        from ..data.keys import encode_key_lanes, exact_string_pool
        from ..types import TypeRoot
        from .changelog import full_compaction_changelog

        before = KVBatch.concat(old_top) if old_top else merged.slice(0, 0)
        key_names = self.merge.key_names
        pools = {}
        for k in key_names:
            root = merged.data.schema.field(k).type.root
            if root in (TypeRoot.CHAR, TypeRoot.VARCHAR, TypeRoot.BINARY, TypeRoot.VARBINARY):
                pools[k] = exact_string_pool([before.data.column(k), merged.data.column(k)])
        lanes_before = encode_key_lanes(before.data, key_names, pools)
        lanes_after = encode_key_lanes(merged.data, key_names, pools)
        return full_compaction_changelog(
            before, merged, lanes_before, lanes_after, row_deduplicate=self.row_deduplicate
        )

    def upgrade(self, file: DataFileMeta, output_level: int) -> DataFileMeta:
        return file.upgrade(output_level)


class MergeTreeCompactManager:
    """Decides when and what to compact for one bucket's Levels. Execution is
    synchronous-on-demand here (deterministic); the async thread-pool offload
    of the reference maps to the parallel runtime's bucket sharding instead."""

    def __init__(
        self,
        levels: Levels,
        strategy: UniversalCompaction,
        rewriter: MergeTreeCompactRewriter,
        options: CoreOptions,
    ):
        self.levels = levels
        self.strategy = strategy
        self.rewriter = rewriter
        self.options = options

    def should_wait_for_compaction(self) -> bool:
        return self.levels.number_of_sorted_runs() > self.options.num_sorted_runs_stop_trigger

    def trigger_compaction(self, full: bool = False) -> CompactResult | None:
        from ..parallel.mesh_exec import current_mesh_context, maybe_mesh_exec
        from ..parallel.pipeline import pipeline_config

        depth, parallelism = pipeline_config(self.options)
        g = compaction_metrics()
        g.counter("rounds").inc()
        with span("compact", histogram=g.histogram("duration_ms"), full=int(full)):
            # merge.engine = mesh and no context installed yet (standalone
            # compaction, not under a table-write batch window): install the
            # MeshExecutor so this bucket's section merges run as batched
            # shard_maps; no-op (yields None) on 1 device — cpu fallback
            with maybe_mesh_exec(self.options) as mex:
                if mex is None and depth > 0 and current_mesh_context() is None:
                    # pipelined route: section reads / device merges / output
                    # encodes overlap (rewrite_pipelined) instead of reading
                    # every input before the first merge. Mesh execution keeps
                    # the dispatch/complete split (all merges in shard_maps).
                    plan = self._plan_unit(full)
                    result = self._complete_pipelined(plan, depth, parallelism)
                else:
                    state = self.compact_dispatch(full)
                    result = self.compact_complete(state)
        if result is not None and not result.is_empty():
            g.counter("compactions").inc()
            g.counter("files_rewritten").inc(len(result.before))
        return result

    def _plan_unit(self, full: bool = False):
        """Pick the unit and classify upgrade-vs-rewrite (reference
        MergeTreeCompactTask.doCompact) WITHOUT reading any input. Returns
        (unit, drop_delete, result, rewrite_sections) or None."""
        with span("compact.pick") as sp:
            runs = self.levels.level_sorted_runs()
            if full:
                unit = self.strategy.force_full(self.levels.num_levels, runs)
            else:
                unit = self.strategy.pick(self.levels.num_levels, runs)
            sp.add(runs=len(runs), files=len(unit.files) if unit is not None else 0)
            if unit is None or not unit.files:
                return None
            return self._classify(unit)

    def _classify(self, unit: CompactUnit):
        """Upgrade or rewrite, section by section, for a picked unit."""
        # drop deletes iff the output is the highest non-empty level's floor
        # (reference MergeTreeCompactManager.triggerCompaction :148-158)
        drop_delete = unit.output_level != 0 and unit.output_level >= self.levels.non_empty_highest_level()
        result = CompactResult()
        sections = IntervalPartition(unit.files).partition()
        rewrite_sections: list[list[SortedRun]] = []
        min_rewrite_size = self.options.target_file_size  # files below target get merged together
        dv_files = set(self.rewriter.deletion_vectors)
        # full-compaction changelog must SEE every row reaching the top level:
        # upgrades bypass rewrite() and would emit nothing (reference forces
        # rewrite when upgrading to maxLevel under the full changelog producer)
        force_rewrite = self.rewriter.emit_full_changelog and drop_delete
        for section in sections:
            if len(section) == 1:
                for f in section[0].files:
                    if f.file_name in dv_files or (force_rewrite and f.level != unit.output_level):
                        # physically drop DV'd rows (the commit purges the DV)
                        rewrite_sections.append([SortedRun([f])])
                    elif self._can_upgrade(f, unit.output_level, drop_delete, min_rewrite_size):
                        if f.level != unit.output_level:
                            up = self.rewriter.upgrade(f, unit.output_level)
                            result.before.append(f)
                            result.after.append(up)
                        # same level: untouched
                    else:
                        rewrite_sections.append([SortedRun([f])])
            else:
                rewrite_sections.append(section)
        return (unit, drop_delete, result, rewrite_sections)

    def compact_dispatch(self, full: bool = False):
        """Phase 1: plan the unit, then read inputs and dispatch the section
        merges (under a mesh context every bucket's merges batch into
        shard_maps over the mesh). Returns opaque state for compact_complete,
        or None when nothing to compact."""
        plan = self._plan_unit(full)
        if plan is None:
            return None
        unit, drop_delete, result, rewrite_sections = plan
        jobs = self.rewriter.rewrite_dispatch(rewrite_sections, unit.output_level) if rewrite_sections else []
        return (unit, drop_delete, result, rewrite_sections, jobs)

    def compact_complete(self, state) -> CompactResult | None:
        """Phase 2: resolve section merges, write outputs, update Levels."""
        if state is None:
            return None
        unit, drop_delete, result, rewrite_sections, jobs = state
        after, changelog = (
            self.rewriter.rewrite_complete(jobs, unit.output_level, drop_delete)
            if rewrite_sections
            else ([], [])
        )
        return self._finish(unit, drop_delete, result, rewrite_sections, after, changelog)

    def _complete_pipelined(self, plan, depth: int, parallelism: int | None) -> CompactResult | None:
        """Pipelined phase 2: sections stream through read -> merge -> encode
        with bounded readahead (rewrite_pipelined) — same outputs, same
        order, without materializing every section's input first."""
        if plan is None:
            return None
        unit, drop_delete, result, rewrite_sections = plan
        after, changelog = (
            self.rewriter.rewrite_pipelined(
                rewrite_sections, unit.output_level, drop_delete, depth, parallelism
            )
            if rewrite_sections
            else ([], [])
        )
        return self._finish(unit, drop_delete, result, rewrite_sections, after, changelog)

    def _finish(
        self, unit, drop_delete, result: CompactResult, rewrite_sections, after, changelog
    ) -> CompactResult:
        """Shared bookkeeping tail: fold rewrite outputs into the result,
        invalidate dead cache entries, update Levels."""
        if rewrite_sections:
            flat_before = [f for sec in rewrite_sections for r in sec for f in r.files]
            self._count_rewrite(unit, rewrite_sections, flat_before, after)
            result.before.extend(flat_before)
            result.after.extend(after)
            result.changelog.extend(changelog)
            # rewritten inputs left the live LSM view: drop their decoded
            # batches so the byte budget tracks the hot working set (upgraded
            # files in result.before keep the same physical file — NOT
            # invalidated; a time-travel read of a rewritten file re-decodes)
            from ..utils.cache import invalidate_data_file

            for f in flat_before:
                invalidate_data_file(f.file_name)
        if not result.is_empty():
            self.levels.update(result.before, result.after)
        return result

    @staticmethod
    def _count_rewrite(unit, rewrite_sections, before, after) -> None:
        """compaction{...} and the open `compact` span for one rewrite: what
        it read and what it wrote (an upgrade writes nothing)."""
        rows_in, rows_out = sum(f.row_count for f in before), sum(f.row_count for f in after)
        g = compaction_metrics()
        g.counter("rows_in").inc(rows_in)
        g.counter("rows_out").inc(rows_out)
        g.counter("files_out").inc(len(after))
        g.counter("bytes_out").inc(sum(f.file_size for f in after))
        sp = span.current()
        if sp is not None and sp.name == "compact":
            sp.add(runs_in=sum(len(sec) for sec in rewrite_sections), rows_in=rows_in, rows_out=rows_out,
                   level_out=unit.output_level)

    @staticmethod
    def _can_upgrade(f: DataFileMeta, output_level: int, drop_delete: bool, min_size: int) -> bool:
        if f.level == 0 and f.file_size < min_size:
            return False  # merge small level-0 files together
        if drop_delete and f.delete_row_count > 0:
            return False  # must rewrite to physically drop deletes at top level
        return True
