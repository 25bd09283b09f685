"""Snapshot scan planning: manifests -> filtered file entries.

Parity: /root/reference/paimon-core/.../operation/AbstractFileStoreScan.plan()
(:221-287 — snapshot -> manifest list -> manifest reads with partition/bucket/
stat/file-index filters) and KeyValueFileStoreScan (key-stat filtering; value
filters are NOT used to skip files for merge-on-read tables because a file
missing a predicate match may still shadow older versions of the key).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Sequence

from ..data.predicate import Predicate
from ..fs import FileIO
from .manifest import FileKind, ManifestEntry, ManifestFile, ManifestList, merge_entries
from .snapshot import Snapshot, SnapshotManager

__all__ = ["ScanPlan", "FileStoreScan"]


@dataclass
class ScanPlan:
    snapshot: Snapshot | None
    entries: list[ManifestEntry] = field(default_factory=list)
    index_entries: list = field(default_factory=list)  # IndexFileEntry

    def grouped(self) -> dict[tuple, dict[int, list]]:
        """{partition: {bucket: [DataFileMeta...]}}"""
        out: dict[tuple, dict[int, list]] = {}
        for e in self.entries:
            out.setdefault(e.partition, {}).setdefault(e.bucket, []).append(e.file)
        return out

    def dv_index_for(self, partition: tuple, bucket: int) -> str | None:
        for e in self.index_entries:
            if e.kind == "DELETION_VECTORS" and e.partition == partition and e.bucket == bucket:
                return e.file_name
        return None

    def dv_indexes(self) -> dict[tuple, str]:
        """{(partition, bucket): dv index file name} for every bucket."""
        return {
            (e.partition, e.bucket): e.file_name
            for e in self.index_entries
            if e.kind == "DELETION_VECTORS"
        }


class FileStoreScan:
    def __init__(
        self,
        file_io: FileIO,
        table_path: str,
        key_names: Sequence[str],
        manifest_parallelism: int | None = None,
        cache=None,
    ):
        self.file_io = file_io
        self.table_path = table_path
        self.key_names = list(key_names)
        self.manifest_parallelism = manifest_parallelism
        # manifest object cache (utils.cache): repeated plan() calls and
        # streaming follow-ups stop re-fetching + re-decoding the snapshot,
        # manifest lists, and manifest files of unchanged history
        self.snapshot_manager = SnapshotManager(file_io, table_path, cache=cache)
        self.manifest_file = ManifestFile(file_io, f"{table_path}/manifest", cache=cache)
        self.manifest_list = ManifestList(file_io, f"{table_path}/manifest", cache=cache)
        self._snapshot_id: int | None = None
        self._kind = "all"  # all | delta | changelog
        self._partition_filter: Callable[[tuple], bool] | None = None
        self._bucket: int | None = None
        self._key_filter: Predicate | None = None
        self._value_filter: Predicate | None = None  # only safe for append tables
        self._level: int | None = None

    # ---- builder -------------------------------------------------------
    def with_snapshot(self, snapshot_id: int) -> "FileStoreScan":
        self._snapshot_id = snapshot_id
        return self

    def with_kind(self, kind: str) -> "FileStoreScan":
        assert kind in ("all", "delta", "changelog")
        self._kind = kind
        return self

    def with_partition_filter(self, fn: Callable[[tuple], bool]) -> "FileStoreScan":
        self._partition_filter = fn
        return self

    def with_bucket(self, bucket: int) -> "FileStoreScan":
        self._bucket = bucket
        return self

    def with_key_filter(self, predicate: Predicate | None) -> "FileStoreScan":
        self._key_filter = predicate
        return self

    def with_value_filter(self, predicate: Predicate | None) -> "FileStoreScan":
        self._value_filter = predicate
        return self

    def with_level(self, level: int) -> "FileStoreScan":
        self._level = level
        return self

    # ---- plan ----------------------------------------------------------
    def plan(self) -> ScanPlan:
        from ..metrics import registry, span

        g = registry.group("scan")
        with span("plan", histogram=g.histogram("duration_ms")) as sp:
            plan = self._plan()
            sp.add(files=len(plan.entries))
        g.counter("plans").inc()
        g.counter("resulted_table_files").inc(len(plan.entries))
        return plan

    def _read_manifests(self, metas) -> list:
        """Manifest files decode independently: scan.manifest.parallelism
        (falling back to scan.parallelism — store.new_scan resolves the
        knobs) threads them over the process-wide shared pool (reference
        ScanParallelExecutor; a pool per plan() would pay thread spawn/join
        on every small scan), order preserved and in-flight bounded."""
        if self.manifest_parallelism and self.manifest_parallelism > 1 and len(metas) > 1:
            from ..parallel.pipeline import bounded_map

            return bounded_map(
                lambda m: self.manifest_file.read(m.file_name), metas, self.manifest_parallelism
            )
        return [self.manifest_file.read(m.file_name) for m in metas]

    def _plan(self) -> ScanPlan:
        if self._snapshot_id is not None:
            snapshot = self.snapshot_manager.snapshot(self._snapshot_id)
        else:
            snapshot = self.snapshot_manager.latest_snapshot()
        if snapshot is None:
            return ScanPlan(None, [])
        if self._kind == "changelog":
            if not snapshot.changelog_manifest_list:
                return ScanPlan(snapshot, [])
            metas = self.manifest_list.read(snapshot.changelog_manifest_list)
            entries = [e for part in self._read_manifests(metas) for e in part]
        elif self._kind == "delta":
            metas = self.manifest_list.read(snapshot.delta_manifest_list)
            entries = [e for part in self._read_manifests(metas) for e in part]
            # delta scans surface ADDs only (changelog semantics come from
            # commit kind + changelog files)
            entries = [e for e in entries if e.kind == FileKind.ADD]
        else:
            metas = self.manifest_list.read(snapshot.base_manifest_list) + self.manifest_list.read(
                snapshot.delta_manifest_list
            )
            entries = merge_entries(*self._read_manifests(metas))
        entries = [e for e in entries if self._accept(e)]
        index_entries = []
        if snapshot.index_manifest:
            from .indexmanifest import read_index_manifest

            for e in read_index_manifest(self.file_io, self.table_path, snapshot.index_manifest):
                if self._partition_filter is not None and not self._partition_filter(e.partition):
                    continue
                if self._bucket is not None and e.bucket != self._bucket:
                    continue
                index_entries.append(e)
        return ScanPlan(snapshot, entries, index_entries)

    def _accept(self, e: ManifestEntry) -> bool:
        if self._partition_filter is not None and not self._partition_filter(e.partition):
            return False
        if self._bucket is not None and e.bucket != self._bucket:
            return False
        if self._level is not None and e.file.level != self._level:
            return False
        if self._key_filter is not None and not self._key_filter.test_stats(e.file.key_stats):
            return False
        if self._value_filter is not None and not self._value_filter.test_stats(e.file.value_stats):
            return False
        return True
