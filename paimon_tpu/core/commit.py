"""The snapshot-CAS commit protocol.

Parity: /root/reference/paimon-core/.../operation/FileStoreCommitImpl.java
(:219 commit, :202-207 filterCommitted via latestSnapshotOfUser, :678 tryCommit
loop, :774 tryCommitOnce, :843-852 manifest merging, :942 atomic snapshot
write, :917 cleanUpTmpManifests) and table/sink/TableCommitImpl.java:183
(filterAndCommit idempotent replay).

One logical commit produces up to two snapshots: APPEND (the writers' new
level-0 files + input changelog) then COMPACT (compaction before/after), same
as the reference — so a crashed commit retried after the APPEND snapshot only
re-applies the missing COMPACT part via commit-identifier filtering.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Callable, Sequence

from ..fs import FileIO
from ..metrics import registry, span
from ..options import CoreOptions
from ..resilience.faults import crash_point
from ..utils import dumps, loads, new_file_name, now_millis
from .manifest import (
    CommitMessage,
    FileKind,
    ManifestCommittable,
    ManifestEntry,
    ManifestFile,
    ManifestFileMeta,
    ManifestList,
    merge_entries,
    merge_entries_keep_deletes,
)
from .snapshot import CommitKind, Snapshot, SnapshotManager

# Batch jobs commit once with this sentinel identifier (reference
# BatchWriteBuilder.COMMIT_IDENTIFIER = Long.MAX_VALUE); it never enters the
# monotonic per-user streaming sequence.
BATCH_COMMIT_IDENTIFIER = (1 << 63) - 1

__all__ = ["FileStoreCommit", "CommitConflictError", "CommitGiveUpError"]


class CommitConflictError(RuntimeError):
    pass


class CommitGiveUpError(RuntimeError):
    """The bounded commit retry loop (commit.max-retries) was exhausted
    without winning the snapshot CAS. The table is untouched by this commit
    (every round's metadata was cleaned up); the committable may be replayed."""


class FileStoreCommit:
    def __init__(
        self,
        file_io: FileIO,
        table_path: str,
        commit_user: str,
        schema_id: int,
        options: CoreOptions | None = None,
        cache=None,
    ):
        self.file_io = file_io
        self.table_path = table_path
        self.commit_user = commit_user
        self.schema_id = schema_id
        self.options = options or CoreOptions()
        # external mutual exclusion where the FS rename is not atomic
        # (reference: commits run under CatalogLock on such stores)
        self._lock = None
        if self.options.options.get(CoreOptions.COMMIT_CATALOG_LOCK) or not getattr(
            file_io, "atomic_write_supported", True
        ):
            lock_type = self.options.options.get(CoreOptions.COMMIT_CATALOG_LOCK_TYPE)
            timeout = self.options.options.get(CoreOptions.COMMIT_CATALOG_LOCK_TIMEOUT)
            stale_ttl = self.options.options.get(CoreOptions.COMMIT_CATALOG_LOCK_STALE_TTL)
            if lock_type == "jdbc":
                from ..catalog.jdbc import JdbcCatalogLock

                db = self.options.options.get(CoreOptions.COMMIT_CATALOG_LOCK_JDBC_PATH)
                if not db:
                    raise ValueError("commit.catalog-lock.type=jdbc needs commit.catalog-lock.jdbc-path")
                self._lock = JdbcCatalogLock(db, lock_id=table_path, timeout=timeout, stale_ttl=stale_ttl)
            elif lock_type == "file":
                if not getattr(file_io, "exclusive_create_supported", True):
                    # a file lock on a store without exclusive create is
                    # check-then-put theater: two holders would both "win"
                    raise ValueError(
                        "this store has no exclusive create (no conditional PUT); "
                        "the file-based catalog lock cannot provide mutual exclusion — "
                        "configure commit.catalog-lock.type=jdbc with "
                        "commit.catalog-lock.jdbc-path"
                    )
                from ..catalog.lock import FileBasedCatalogLock

                self._lock = FileBasedCatalogLock(file_io, table_path, timeout=timeout, stale_ttl=stale_ttl)
            else:
                raise ValueError(f"unknown commit.catalog-lock.type: {lock_type!r} (expected 'file' or 'jdbc')")
        # manifest object cache: every commit re-reads the latest snapshot's
        # base+delta manifests (conflict check, manifest merge) — immutable
        # files, so the decoded entries come from the shared cache
        self.snapshot_manager = SnapshotManager(file_io, table_path, cache=cache)
        self.manifest_file = ManifestFile(file_io, f"{table_path}/manifest", cache=cache)
        self.manifest_list = ManifestList(file_io, f"{table_path}/manifest", cache=cache)

    # ---- idempotence ----------------------------------------------------
    def filter_committed(self, committables: Sequence[ManifestCommittable]) -> list[ManifestCommittable]:
        """Drop committables whose identifier this user already committed
        (crash-replay safety; reference FileStoreCommit.filterCommitted).

        Only streaming committables route through here (batch commits carry
        the sentinel identifier and skip the filter), so the watermark is the
        user's latest NON-sentinel snapshot: a batch maintenance commit by
        the same user must not make every pending streaming identifier look
        already-committed (the reference avoids this only by convention —
        fresh UUID commit users per job)."""
        latest_of_user = None
        for snap in self.snapshot_manager.snapshots_of_user(self.commit_user):
            if snap.commit_identifier != BATCH_COMMIT_IDENTIFIER:
                latest_of_user = snap
                break
        if latest_of_user is None:
            return list(committables)
        done = latest_of_user.commit_identifier
        out: list[ManifestCommittable] = []
        for c in committables:
            if c.commit_identifier > done:
                out.append(c)
            elif c.commit_identifier == done:
                # the APPEND snapshot landed; keep the committable (flagged to
                # skip its APPEND phase) if its COMPACT half is still missing
                has_compact = any(m.compact_before or m.compact_after for m in c.messages)
                if has_compact:
                    kinds = {
                        s.commit_kind
                        for s in self.snapshot_manager.snapshots_of_user_with_identifier(
                            self.commit_user, c.commit_identifier
                        )
                    }
                    if CommitKind.COMPACT not in kinds:
                        out.append(replace(c, skip_append=True))
        return out

    # ---- commit ---------------------------------------------------------
    def commit(self, committable: ManifestCommittable) -> list[int]:
        """Returns the snapshot ids written (0, 1, or 2)."""
        # an operation of its own where none is open: the committer is another
        # actor than the writer (a sink's committer operator)
        with span("commit", new_op=span.current() is None) as sp:
            written = self._commit(committable)
            sp.add(snapshots=len(written))
        return written

    def _commit(self, committable: ManifestCommittable) -> list[int]:
        append_entries: list[ManifestEntry] = []
        compact_entries: list[ManifestEntry] = []
        append_changelog: list[ManifestEntry] = []
        compact_changelog: list[ManifestEntry] = []
        for msg in committable.messages:
            for f in msg.new_files:
                append_entries.append(ManifestEntry(FileKind.ADD, msg.partition, msg.bucket, msg.total_buckets, f))
            for f in msg.compact_before:
                compact_entries.append(ManifestEntry(FileKind.DELETE, msg.partition, msg.bucket, msg.total_buckets, f))
            for f in msg.compact_after:
                compact_entries.append(ManifestEntry(FileKind.ADD, msg.partition, msg.bucket, msg.total_buckets, f))
            for f in msg.changelog_files:
                append_changelog.append(ManifestEntry(FileKind.ADD, msg.partition, msg.bucket, msg.total_buckets, f))
            for f in msg.compact_changelog_files:
                compact_changelog.append(ManifestEntry(FileKind.ADD, msg.partition, msg.bucket, msg.total_buckets, f))
        index_entries = [e for msg in committable.messages for e in msg.new_index_files]
        written: list[int] = []
        if not committable.skip_append and (
            append_entries or index_entries or append_changelog or not compact_entries
        ):
            written.append(
                self._try_commit(
                    CommitKind.APPEND,
                    append_entries,
                    committable,
                    check_conflicts=False,
                    index_entries=index_entries,
                    changelog_entries=append_changelog,
                )
            )
            # from here the APPEND snapshot is durable: flag the committable so
            # a caller retrying it (or replaying via filter_committed) cannot
            # double-apply the APPEND phase if COMPACT fails below
            committable.skip_append = True
        if compact_entries:
            # purge DVs only for files that truly disappear: an upgrade emits
            # DELETE+ADD with the SAME file name (level change only) and its
            # DV must survive
            added_names = {e.file.file_name for e in compact_entries if e.kind == FileKind.ADD}
            removed = [
                e
                for e in compact_entries
                if e.kind == FileKind.DELETE and e.file.file_name not in added_names
            ]
            written.append(
                self._try_commit(
                    CommitKind.COMPACT,
                    compact_entries,
                    committable,
                    check_conflicts=True,
                    removed_files=removed,
                    changelog_entries=compact_changelog,
                )
            )
        return [w for w in written if w >= 0]

    def overwrite(
        self,
        committable: ManifestCommittable,
        partition_filter: Callable[[tuple], bool] | None = None,
    ) -> list[int]:
        """INSERT OVERWRITE: logically delete current files (of the matching
        partitions), then add the new ones, in one OVERWRITE snapshot."""
        latest = self.snapshot_manager.latest_snapshot()
        entries: list[ManifestEntry] = []
        if latest is not None:
            for e in self._live_entries(latest):
                if partition_filter is None or partition_filter(e.partition):
                    entries.append(ManifestEntry(FileKind.DELETE, e.partition, e.bucket, e.total_buckets, e.file))
        for msg in committable.messages:
            for f in msg.new_files:
                entries.append(ManifestEntry(FileKind.ADD, msg.partition, msg.bucket, msg.total_buckets, f))
        return [self._try_commit(CommitKind.OVERWRITE, entries, committable, check_conflicts=False)]

    # ---- internals ------------------------------------------------------
    def _live_entries(self, snapshot: Snapshot) -> list[ManifestEntry]:
        metas = self.manifest_list.read(snapshot.base_manifest_list) + self.manifest_list.read(
            snapshot.delta_manifest_list
        )
        return merge_entries(*(self.manifest_file.read(m.file_name) for m in metas))

    def _index_manifest(
        self, latest: Snapshot | None, index_entries: list, removed_files: list[ManifestEntry] | None = None
    ) -> str | None:
        """New index manifest = previous entries with same-(partition, bucket,
        kind) slots replaced by this commit's entries (a maintainer always
        emits the complete replacement set for its bucket). For commits that
        remove data files (COMPACT/OVERWRITE), deletion vectors of the dead
        files are purged — their rows were physically dropped during the
        rewrite, and keeping stale DVs would desynchronize the index."""
        from .deletionvectors import DeletionVectorsIndexFile
        from .indexmanifest import read_index_manifest, write_index_manifest

        prev: list = []
        if latest is not None and latest.index_manifest:
            prev = read_index_manifest(self.file_io, self.table_path, latest.index_manifest)
        dead_by_pb: dict[tuple, set] = {}
        for e in removed_files or []:
            dead_by_pb.setdefault((e.partition, e.bucket), set()).add(e.file.file_name)
        if not index_entries and not dead_by_pb:
            return latest.index_manifest if latest else None
        replaced = {(e.partition, e.bucket, e.kind) for e in index_entries}
        out = []
        dv_io = DeletionVectorsIndexFile(
            self.file_io,
            self.table_path,
            target_size=int(
                self.options.options.get(CoreOptions.DELETION_VECTOR_INDEX_FILE_TARGET_SIZE)
            ),
        )
        for e in prev:
            if (e.partition, e.bucket, e.kind) in replaced:
                continue
            dead = dead_by_pb.get((e.partition, e.bucket))
            if dead and e.kind == "DELETION_VECTORS":
                dvs = dv_io.read_all(e.file_name)
                live = {f: dv for f, dv in dvs.items() if f not in dead}
                if not live:
                    continue
                if len(live) != len(dvs):
                    name, total = dv_io.write(live)
                    from .deletionvectors import IndexFileEntry

                    e = IndexFileEntry(e.kind, e.partition, e.bucket, name, total)
            out.append(e)
        out.extend(index_entries)
        if not out:
            return None
        return write_index_manifest(self.file_io, self.table_path, out)

    def _try_commit(
        self,
        kind: CommitKind,
        entries: list[ManifestEntry],
        committable: ManifestCommittable,
        check_conflicts: bool,
        index_entries: list | None = None,
        removed_files: list[ManifestEntry] | None = None,
        changelog_entries: list[ManifestEntry] | None = None,
        statistics: str | None = None,
    ) -> int:
        import random
        import time

        g = registry.group("commit")
        opts = self.options.options
        max_retries = opts.get(CoreOptions.COMMIT_MAX_RETRIES)
        backoff_base = float(opts.get(CoreOptions.COMMIT_RETRY_BACKOFF))
        prev_backoff: float | None = None
        retries = 0
        t_start = time.perf_counter()
        from contextlib import nullcontext

        while True:
            with self._lock.lock() if self._lock is not None else nullcontext():
                latest = self.snapshot_manager.latest_snapshot()
                if check_conflicts and latest is not None:
                    conflicted = self._conflicted_buckets(latest, entries)
                    if conflicted:
                        g.counter("conflicts").inc()
                        all_buckets = {(e.partition, e.bucket) for e in entries}
                        if all_buckets <= conflicted:
                            raise CommitConflictError(
                                f"files of bucket(s) {sorted(conflicted)} were removed by a "
                                f"concurrent commit; giving up this {kind.value} commit"
                            )
                        # retriable conflict: only SOME buckets lost their
                        # inputs to a concurrent commit. Abandon those (their
                        # rewritten outputs become orphans, reclaimed by
                        # remove_orphan_files) and re-plan the untouched
                        # buckets against the new latest — finer-grained than
                        # the seed's whole-commit abort.
                        g.counter("buckets_abandoned").inc(len(conflicted))
                        entries = [e for e in entries if (e.partition, e.bucket) not in conflicted]
                        removed_files = [
                            e for e in (removed_files or []) if (e.partition, e.bucket) not in conflicted
                        ]
                        changelog_entries = [
                            e for e in (changelog_entries or []) if (e.partition, e.bucket) not in conflicted
                        ]
                        index_entries = [
                            ie for ie in (index_entries or []) if (ie.partition, ie.bucket) not in conflicted
                        ]
                crash_point("commit:before-manifests")
                tmp_files: list[str] = []
                try:
                    snapshot_id = (latest.id + 1) if latest else 1
                    base_metas = (
                        self.manifest_list.read(latest.base_manifest_list)
                        + self.manifest_list.read(latest.delta_manifest_list)
                        if latest
                        else []
                    )
                    base_metas = self._maybe_merge_manifests(base_metas, tmp_files)
                    delta_meta = self.manifest_file.write(entries, self.schema_id, track=tmp_files)
                    base_name = self.manifest_list.write(base_metas, track=tmp_files)
                    delta_name = self.manifest_list.write([delta_meta], track=tmp_files)
                    changelog_list = None
                    changelog_rows = None
                    if changelog_entries:
                        cl_meta = self.manifest_file.write(changelog_entries, self.schema_id, track=tmp_files)
                        changelog_list = self.manifest_list.write([cl_meta], track=tmp_files)
                        changelog_rows = sum(e.file.row_count for e in changelog_entries)
                    added = sum(e.file.row_count for e in entries if e.kind == FileKind.ADD)
                    deleted = sum(e.file.row_count for e in entries if e.kind == FileKind.DELETE)
                    prev_total = (latest.total_record_count or 0) if latest else 0
                    index_manifest = self._index_manifest(latest, index_entries or [], removed_files)
                    if index_manifest and index_manifest != (latest.index_manifest if latest else None):
                        # freshly written this round: clean it up with the
                        # other metadata if the CAS is lost/aborted (the seed
                        # leaked it)
                        tmp_files.append(index_manifest)
                    snapshot = Snapshot(
                        id=snapshot_id,
                        schema_id=self.schema_id,
                        base_manifest_list=base_name,
                        delta_manifest_list=delta_name,
                        changelog_manifest_list=changelog_list,
                        commit_user=self.commit_user,
                        commit_identifier=committable.commit_identifier,
                        commit_kind=kind,
                        time_millis=now_millis(),
                        index_manifest=index_manifest,
                        total_record_count=prev_total + added - deleted,
                        delta_record_count=added - deleted,
                        changelog_record_count=changelog_rows,
                        statistics=statistics,
                        watermark=committable.watermark,
                        log_offsets=dict(committable.log_offsets),
                    )
                    crash_point("commit:manifests-written")
                    path = self.snapshot_manager.snapshot_path(snapshot_id)
                    if self.file_io.try_atomic_write(path, snapshot.to_json().encode()):
                        g.counter("commits").inc()
                        g.counter("retries").inc(retries)
                        sp = span.current()
                        if sp is not None and sp.name == "commit":
                            sp.add(entries=len(entries), retries=retries)
                        g.histogram("duration_ms").update((time.perf_counter() - t_start) * 1000)
                        # committed: the snapshot now references these manifests —
                        # they must never be cleaned up, even if hints fail
                        tmp_files.clear()
                        crash_point("commit:snapshot-committed")
                        try:
                            self.snapshot_manager.commit_latest_hint(snapshot_id)
                            if snapshot_id == 1:
                                self.snapshot_manager.commit_earliest_hint(1)
                        except Exception:
                            pass  # hints are best-effort; listing is authoritative
                        return snapshot_id
                    # lost the CAS race. First: did OUR commit actually land?
                    # (an IO-layer retry of a rename whose ack was lost, or a
                    # replay racing its own earlier attempt) — adopting it
                    # instead of re-committing prevents double-apply.
                    own = self._find_own_commit(snapshot_id, committable, kind, delta_name)
                    if own is not None:
                        self._cleanup_after_adopt(own, tmp_files)
                        return own
                    # genuinely lost to another committer: clean this round's
                    # metadata and retry against the new latest
                    self._cleanup(tmp_files)
                    retries += 1
                    if retries > max_retries:
                        raise CommitGiveUpError(
                            f"commit lost the snapshot race {retries} times "
                            f"(commit.max-retries={max_retries}); giving up"
                        )
                except Exception:
                    # an exception may have escaped mid-write, so this is the
                    # one path where torn tmp siblings can exist
                    self._cleanup(tmp_files, sweep_torn=True)
                    raise
                # a simulated CrashError (BaseException) bypasses the cleanup
                # above on purpose: a killed process runs no cleanup either —
                # recovery is remove_orphan_files' job
            # backoff OUTSIDE the lock so racing committers make progress;
            # decorrelated jitter desynchronizes the herd
            if backoff_base > 0:
                hi = min(backoff_base * 100.0, max(backoff_base, (prev_backoff or backoff_base) * 3.0))
                prev_backoff = random.uniform(backoff_base, hi)
                time.sleep(prev_backoff / 1000.0)

    def _conflicted_buckets(self, latest: Snapshot, entries: list[ManifestEntry]) -> set[tuple]:
        """(partition, bucket) slots whose logically-deleted files are no
        longer live (reference noConflictsOrFail :804-808 — a concurrent
        compaction removing the same files is a conflict; the loser abandons
        that bucket's compaction)."""
        deletes = [e for e in entries if e.kind == FileKind.DELETE]
        if not deletes:
            return set()
        live = {(e.partition, e.bucket, e.file.file_name) for e in self._live_entries(latest)}
        return {
            (e.partition, e.bucket)
            for e in deletes
            if (e.partition, e.bucket, e.file.file_name) not in live
        }

    def _find_own_commit(
        self, from_id: int, committable: ManifestCommittable, kind: CommitKind, delta_name: str
    ) -> int | None:
        """After a lost CAS at `from_id`: the id of an already-landed snapshot
        that is OURS, or None. Two proofs of ownership:

        - content: the snapshot at `from_id` references the uuid-named delta
          manifest list written THIS round — only our own rename (whose ack
          was lost and whose IO-layer retry then saw `path exists` → False)
          can have published those bytes. This also covers batch/maintenance
          commits, whose sentinel identifier proves nothing.
        - identity: a snapshot carrying our (user, identifier, kind) — covers
          a crash-replay racing its own earlier attempt, which wrote its own
          manifest copies. Sentinel identifiers are shared across logical
          commits and are excluded from this scan.
        """
        if self.snapshot_manager.snapshot_exists(from_id):
            try:
                snap = self.snapshot_manager.snapshot(from_id)
            except Exception:
                snap = None  # racing expiry etc.; fall through to identity
            if snap is not None and snap.delta_manifest_list == delta_name:
                return from_id
        ident = committable.commit_identifier
        if ident >= BATCH_COMMIT_IDENTIFIER - 16:
            return None
        latest_id = self.snapshot_manager.latest_snapshot_id()
        if latest_id is None:
            return None
        for sid in range(from_id, latest_id + 1):
            if not self.snapshot_manager.snapshot_exists(sid):
                continue
            snap = self.snapshot_manager.snapshot(sid)
            if (
                snap.commit_user == self.commit_user
                and snap.commit_identifier == ident
                and snap.commit_kind == kind
            ):
                return sid
        return None

    def _cleanup_after_adopt(self, own_id: int, tmp_files: list[str]) -> None:
        """Cleanup after adopting an already-landed snapshot as our own. In
        the lost-rename-ack case the adopted snapshot IS this round's bytes:
        every manifest it references is live and must survive cleanup, or the
        latest snapshot dangles and the table is unreadable. A rival replay
        wrote its own manifest copies, so nothing intersects and this round's
        metadata is swept as usual. If the adopted snapshot cannot be re-read
        we leak rather than delete: the orphan sweep reclaims true orphans
        later, while a wrong delete here is unrecoverable."""
        try:
            snap = self.snapshot_manager.snapshot(own_id)
            live = {
                n
                for n in (
                    snap.base_manifest_list,
                    snap.delta_manifest_list,
                    snap.changelog_manifest_list,
                    snap.index_manifest,
                )
                if n
            }
            for lst in (
                snap.base_manifest_list,
                snap.delta_manifest_list,
                snap.changelog_manifest_list,
            ):
                if lst:
                    live.update(m.file_name for m in self.manifest_list.read(lst))
        except Exception:
            tmp_files.clear()
            return
        tmp_files[:] = [n for n in tmp_files if n not in live]
        self._cleanup(tmp_files)

    def _maybe_merge_manifests(
        self, metas: list[ManifestFileMeta], tmp_files: list[str]
    ) -> list[ManifestFileMeta]:
        """Compact many small manifests into fewer big ones (reference
        ManifestFileMeta.merge at commit :843-852). Two triggers:
        - count: >= manifest.merge-min-count small manifests merge together
          (DELETE entries survive — older manifests may still reference them)
        - size (full compaction, reference manifest.full-compaction-threshold-size):
          once the small/unmerged manifests exceed the threshold bytes, ALL
          manifests rewrite into fresh compacted ones; with the whole history
          merged, DELETE entries resolve away entirely."""
        min_count = self.options.options.get(CoreOptions.MANIFEST_MERGE_MIN_COUNT)
        target = int(self.options.options.get(CoreOptions.MANIFEST_TARGET_SIZE))
        full_threshold = int(
            self.options.options.get(CoreOptions.MANIFEST_FULL_COMPACTION_THRESHOLD_SIZE)
        )
        small = [m for m in metas if m.file_size < target]
        total_bytes = sum(m.file_size for m in metas)
        # convergence guard: a full compaction's own output is ~ideal_chunks
        # manifests; only re-trigger when the history is genuinely fragmented
        # beyond that, or every commit would rewrite everything (quadratic)
        ideal_chunks = max(1, -(-total_bytes // target))
        fragmented = len(metas) > 2 * ideal_chunks
        if small and fragmented and sum(m.file_size for m in small) >= full_threshold:
            entries = merge_entries(*(self.manifest_file.read(m.file_name) for m in metas))
            out, small, big = [], [], []  # rewrite everything below
        elif len(small) < min_count:
            return metas
        else:
            big = [m for m in metas if m.file_size >= target]
            entries = merge_entries_keep_deletes(*(self.manifest_file.read(m.file_name) for m in small))
            out = list(big)
        if entries:
            # chunk to target size with an ADAPTIVE bytes/entry estimate:
            # after each write the measured size corrects the next chunk, so
            # outputs land near target regardless of compression ratio
            per_entry = 400.0
            i = 0
            while i < len(entries):
                per_file = max(1, int(target / per_entry))
                chunk = entries[i : i + per_file]
                meta = self.manifest_file.write(chunk, self.schema_id, track=tmp_files)
                out.append(meta)
                per_entry = max(1.0, meta.file_size / max(len(chunk), 1))
                i += len(chunk)
        return out

    def _cleanup(self, names: list[str], sweep_torn: bool = False) -> None:
        """Best-effort removal of this round's metadata after an abort or a
        lost CAS race: the tracked manifest names and — only when `sweep_torn`
        — their torn `.tmp.*` siblings (an atomic write that failed between
        tmp write and rename leaves one; names are tracked BEFORE any byte is
        written, so even a write that died mid-flight is covered). A lost-CAS
        round completed every write, and a completed try_atomic_write leaves
        no torn sibling, so those rounds skip the directory LIST entirely (an
        object-store LIST per retry round is real money). Failures are
        non-fatal (the original error must win; leftovers become orphans for
        remove_orphan_files) and are counted in io{cleanup_failures} — except
        a missing manifest dir, which just means the round died before its
        first byte landed."""
        if not names:
            return
        from ..metrics import io_metrics

        g = io_metrics()
        mdir = f"{self.table_path}/manifest"
        siblings: dict[str, list[str]] = {}
        if sweep_torn:
            try:
                for st in self.file_io.list_files(mdir):
                    base = st.path.rsplit("/", 1)[-1]
                    if base.startswith(".") and base.endswith(".tmp"):
                        # .<name>.<hex>.tmp -> <name>; only OUR tracked names are
                        # swept (a concurrent committer's in-flight tmp must live).
                        # Path rebuilt from mdir: wrapper FileIOs list inner paths.
                        siblings.setdefault(base[1:].rsplit(".", 2)[0], []).append(f"{mdir}/{base}")
            except FileNotFoundError:
                pass  # dir never created: nothing to sweep
            except Exception:
                g.counter("cleanup_failures").inc()
        for name in names:
            for target in (f"{mdir}/{name}", *siblings.get(name, ())):
                try:
                    self.file_io.delete(target)
                except Exception:
                    g.counter("cleanup_failures").inc()
        names.clear()
