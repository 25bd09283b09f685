"""Typed configuration system.

Capability parity with the reference options kernel
(/root/reference/paimon-common/.../options/Options.java, ConfigOption with
typed defaults + fallback keys; CoreOptions.java — the table option surface
with MergeEngine/StartupMode/ChangelogProducer/SortEngine enums). Options are
plain string maps persisted inside the schema JSON; ConfigOption gives them
types, defaults, and fallback keys.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Any, Callable, Generic, Mapping, TypeVar

T = TypeVar("T")

__all__ = [
    "ConfigOption",
    "Options",
    "MemorySize",
    "CoreOptions",
    "MergeEngine",
    "StartupMode",
    "ChangelogProducer",
    "SortEngine",
    "BucketMode",
]


_DURATION_UNITS = {
    "ms": 1,
    "s": 1000,
    "sec": 1000,
    "min": 60_000,
    "m": 60_000,
    "h": 3_600_000,
    "d": 86_400_000,
}


def parse_duration_millis(v: "str | int | float") -> int:
    """'1 h' / '30s' / '100 ms' / bare number (millis) -> millis int
    (reference TimeUtils.parseDuration)."""
    if isinstance(v, (int, float)):
        return int(v)
    t = str(v).strip().lower().replace(" ", "")
    for u in ("ms", "sec", "min", "s", "m", "h", "d"):
        if t.endswith(u) and t[: -len(u)].replace(".", "", 1).isdigit():
            return int(float(t[: -len(u)]) * _DURATION_UNITS[u])
    return int(float(t))


class MemorySize(int):
    """Bytes, parseable from '128 mb' style strings."""

    _UNITS = {"b": 1, "kb": 1 << 10, "mb": 1 << 20, "gb": 1 << 30, "tb": 1 << 40}

    @staticmethod
    def parse(s: "str | int | MemorySize") -> "MemorySize":
        if isinstance(s, int):
            return MemorySize(s)
        t = s.strip().lower().replace(" ", "")
        for u in ("tb", "gb", "mb", "kb", "b"):
            if t.endswith(u):
                return MemorySize(int(float(t[: -len(u)]) * MemorySize._UNITS[u]))
        return MemorySize(int(t))

    def __str__(self) -> str:
        return f"{int(self)} b"


@dataclass(frozen=True)
class ConfigOption(Generic[T]):
    key: str
    default: T
    parser: Callable[[Any], T]
    description: str = ""
    fallback_keys: tuple[str, ...] = ()

    @staticmethod
    def string(key: str, default: str | None = None, description: str = "", fallback: tuple[str, ...] = ()):
        return ConfigOption(key, default, lambda v: None if v is None else str(v), description, fallback)

    @staticmethod
    def int_(key: str, default: int | None = None, description: str = "", fallback: tuple[str, ...] = ()):
        return ConfigOption(key, default, lambda v: None if v is None else int(v), description, fallback)

    @staticmethod
    def float_(key: str, default: float | None = None, description: str = ""):
        return ConfigOption(key, default, lambda v: None if v is None else float(v), description)

    @staticmethod
    def bool_(key: str, default: bool = False, description: str = "", fallback: tuple[str, ...] = ()):
        return ConfigOption(
            key, default, lambda v: v if isinstance(v, bool) else str(v).lower() == "true", description, fallback
        )

    @staticmethod
    def memory(key: str, default: str, description: str = ""):
        return ConfigOption(key, MemorySize.parse(default), MemorySize.parse, description)

    @staticmethod
    def duration(key: str, default: "str | None", description: str = "", fallback: tuple[str, ...] = ()):
        """Duration in MILLIS, parsed from '1 h' / '30 s' / '100 ms' / bare
        millis (reference TimeUtils.parseDuration). Value type: int | None."""
        d = None if default is None else parse_duration_millis(default)
        return ConfigOption(key, d, lambda v: None if v is None else parse_duration_millis(v), description, fallback)

    @staticmethod
    def enum(key: str, enum_cls, default, description: str = "", fallback: tuple[str, ...] = ()):
        def parse(v):
            if isinstance(v, enum_cls):
                return v
            return enum_cls(str(v).lower().replace("_", "-"))

        return ConfigOption(key, default, parse, description, fallback)


class Options:
    """A string->value map with typed access via ConfigOption."""

    def __init__(self, data: Mapping[str, Any] | None = None):
        self._data: dict[str, Any] = dict(data or {})

    def get(self, option: ConfigOption[T]) -> T:
        for key in (option.key, *option.fallback_keys):
            if key in self._data:
                return option.parser(self._data[key])
        return option.default

    def set(self, option: "ConfigOption | str", value: Any) -> "Options":
        key = option if isinstance(option, str) else option.key
        self._data[key] = value
        return self

    def contains(self, option: "ConfigOption | str") -> bool:
        key = option if isinstance(option, str) else option.key
        return key in self._data

    def remove(self, key: str) -> None:
        self._data.pop(key, None)

    def to_map(self) -> dict[str, str]:
        return {k: (v if isinstance(v, str) else str(v)) for k, v in self._data.items()}

    def copy(self) -> "Options":
        return Options(self._data)

    def update(self, other: "Options | Mapping[str, Any]") -> "Options":
        self._data.update(other._data if isinstance(other, Options) else other)
        return self

    def __eq__(self, o):
        return isinstance(o, Options) and self._data == o._data

    def __repr__(self):
        return f"Options({self._data})"


# ---- enums mirroring CoreOptions (reference CoreOptions.java:1937,1966,2107,2321)


class MergeEngine(str, enum.Enum):
    DEDUPLICATE = "deduplicate"
    PARTIAL_UPDATE = "partial-update"
    AGGREGATE = "aggregation"
    FIRST_ROW = "first-row"


class StartupMode(str, enum.Enum):
    DEFAULT = "default"
    LATEST_FULL = "latest-full"
    LATEST = "latest"
    FROM_TIMESTAMP = "from-timestamp"
    FROM_SNAPSHOT = "from-snapshot"
    FROM_SNAPSHOT_FULL = "from-snapshot-full"
    COMPACTED_FULL = "compacted-full"

    @classmethod
    def _missing_(cls, value):
        if value == "full":  # deprecated legacy value (reference StartupMode.FULL)
            return cls.LATEST_FULL
        return None


class ChangelogProducer(str, enum.Enum):
    NONE = "none"
    INPUT = "input"
    FULL_COMPACTION = "full-compaction"
    LOOKUP = "lookup"


class SortEngine(str, enum.Enum):
    XLA_SEGMENTED = "xla-segmented"  # device sort+segment-reduce (default)
    PALLAS = "pallas"  # lax.sort + pallas boundary/keep-last sweep kernel
    NUMPY = "numpy"  # host oracle


class BucketMode(str, enum.Enum):
    FIXED = "fixed"
    DYNAMIC = "dynamic"
    UNAWARE = "unaware"


class CoreOptions:
    """The table option surface (reference CoreOptions.java — 149 options;
    the ones that drive behavior here, same keys where concepts map 1:1)."""

    BUCKET = ConfigOption.int_("bucket", -1, "Number of buckets (-1 = dynamic/unaware).")
    BUCKET_KEY = ConfigOption.string("bucket-key", None, "Comma-separated bucket key columns (default: primary key).")
    PATH = ConfigOption.string("path", None, "Table path.")
    FILE_FORMAT = ConfigOption.string("file.format", "parquet", "Data file format: parquet|orc|lance.")
    FILE_COMPRESSION = ConfigOption.string("file.compression", "zstd", "Data file compression codec.")
    FILE_COMPRESSION_ZSTD_LEVEL = ConfigOption.int_(
        "file.compression.zstd-level", 1, "zstd level for data files (higher = smaller + slower)."
    )
    FILE_COMPRESSION_PER_LEVEL = ConfigOption.string(
        "file.compression.per.level",
        None,
        "Per-LSM-level compression override, e.g. '0:lz4,5:zstd' (level-0 "
        "files are short-lived: cheap codec; bottom level: dense codec).",
    )
    FILE_FORMAT_PER_LEVEL = ConfigOption.string(
        "file.format.per.level",
        None,
        "Per-LSM-level format override, e.g. '0:avro,5:parquet' (row format "
        "for hot small runs, columnar for the settled bottom level).",
    )
    FILE_BLOCK_SIZE = ConfigOption(
        "file.block-size",
        None,
        lambda v: None if v is None else MemorySize.parse(v),
        "Write block size: orc stripe / parquet row-group bytes.",
    )
    PARQUET_ENABLE_DICTIONARY = ConfigOption.bool_(
        "parquet.enable.dictionary", True, "Dictionary encoding for parquet data files."
    )
    FORMAT_PARQUET_DECODER = ConfigOption.string(
        "format.parquet.decoder",
        "arrow",
        "Parquet read decoder: 'arrow' (pyarrow C++ columnar decode) or "
        "'native' (paimon_tpu.decode: thrift-parsed pages, vectorized "
        "RLE/dict/delta kernels, compressed-domain predicate pushdown that "
        "expands only surviving pages; falls back to arrow per file on "
        "unsupported container features).",
    )
    FORMAT_PARQUET_ENCODER = ConfigOption.string(
        "format.parquet.encoder",
        "arrow",
        "Parquet write encoder: 'arrow' (ColumnBatch.to_arrow + pyarrow "
        "pq.write_table) or 'native' (paimon_tpu.encode: vectorized "
        "PLAIN/RLE/DELTA/dictionary kernels writing pages straight from "
        "columnar arrays, reusing the merge path's string pools for "
        "dictionary pages; falls back to arrow per file on unsupported "
        "shapes such as nested columns).",
    )
    READ_BATCH_SIZE = ConfigOption.int_(
        "read.batch-size", None, "Rows per record batch handed to engine surfaces (unset: 1M-row chunks)."
    )
    MANIFEST_FORMAT = ConfigOption.string("manifest.format", "jsonl", "Manifest file format.")
    MANIFEST_COMPRESSION = ConfigOption.string(
        "manifest.compression",
        "default",
        "Manifest codec: default (zstd for jsonl / deflate for avro) or none.",
    )
    TARGET_FILE_SIZE = ConfigOption.memory("target-file-size", "128 mb", "Rolling target size for data files.")
    WRITE_BUFFER_SIZE = ConfigOption.memory("write-buffer-size", "256 mb", "Memtable size before flush.")
    WRITE_BUFFER_ROWS = ConfigOption.int_("write-buffer-rows", 1_000_000, "Memtable row cap before flush.")
    WRITE_ONLY = ConfigOption.bool_(
        "write-only",
        False,
        "Skip compaction (dedicated compact job mode).",
        fallback=("write.compaction-skip",),
    )
    WRITE_BUFFER_MAX_MEMORY = ConfigOption.memory(
        "write.buffer.max-memory",
        "0 b",
        "Admission-control byte budget over ALL buffered memtables and "
        "in-flight offloaded flushes of a write job (0 = off). Above "
        "write.buffer.stop-trigger of this budget new writes first throttle "
        "(bounded block while flushes drain, deadline "
        "write.buffer.block-timeout) and then reject with "
        "WriterBackpressureError.",
    )
    WRITE_BUFFER_STOP_TRIGGER = ConfigOption.float_(
        "write.buffer.stop-trigger",
        0.9,
        "Fraction of write.buffer.max-memory at which incoming writes stop "
        "being admitted immediately and start throttling.",
    )
    WRITE_BUFFER_BLOCK_TIMEOUT = ConfigOption.duration(
        "write.buffer.block-timeout",
        "10 s",
        "How long a throttled write blocks waiting for flushes to release "
        "buffer budget before it is rejected with WriterBackpressureError.",
    )
    WRITE_BUFFER_MAX_PENDING_FLUSHES = ConfigOption.int_(
        "write.buffer.max-pending-flushes",
        4,
        "Cap on memtables queued behind the offloaded flush workers across "
        "a write job (0 = unlimited). At the cap the writer encodes inline — "
        "the caller pays — so a slow encoder can never queue unbounded "
        "memtables.",
    )
    WRITE_BUFFER_SPILLABLE = ConfigOption.bool_(
        "write-buffer-spillable", False, "Spill the write buffer to local disk under memory pressure."
    )
    WRITE_BUFFER_SPILL_ROWS = ConfigOption.int_(
        "write-buffer-spill.rows", 256 * 1024, "In-memory rows before a spill segment is written."
    )
    LOCAL_MERGE_BUFFER_SIZE = ConfigOption.memory(
        "local-merge-buffer-size",
        "0 b",
        "When >0, pre-merge high-churn keys in a local buffer BEFORE bucket "
        "routing (reference LocalMergeOperator; deduplicate engine only).",
    )
    WRITE_BUFFER_SPILL_SIZE = ConfigOption.memory(
        "write-buffer-spill.size", "64 mb", "In-memory bytes before a spill segment is written."
    )
    MERGE_ENGINE = ConfigOption.enum("merge-engine", MergeEngine, MergeEngine.DEDUPLICATE, "How same-key records merge.")
    IGNORE_DELETE = ConfigOption.bool_(
        "ignore-delete",
        False,
        "Ignore -D records on write/merge.",
        fallback=(
            "first-row.ignore-delete",
            "deduplicate.ignore-delete",
            "partial-update.ignore-delete",
        ),
    )
    SORT_ENGINE = ConfigOption.enum("sort-engine", SortEngine, SortEngine.XLA_SEGMENTED, "Merge kernel backend.")
    MERGE_LANE_COMPRESSION = ConfigOption.bool_(
        "merge.lane-compression",
        True,
        "Compress uint32 key lanes before every merge, compaction rewrite, "
        "and sort-compact sort: drop batch-constant lanes, bit-pack adjacent "
        "narrowed lanes into fused uint32 operands, and lead wide keys with "
        "a device-computed offset-value code lane (OVC). Output is "
        "bit-identical to the uncompressed path; off restores it.",
    )
    MERGE_DICT_DOMAIN = ConfigOption.bool_(
        "merge.dict-domain",
        False,
        "Carry dictionary codes as the merge currency end-to-end: readers "
        "return (pool, codes) columns for dictionary-encoded string/bytes "
        "chunks instead of expanding them, per-file pools unify into one "
        "sorted merge domain (ops.dicts — the LSM-OPD/LUDA move), re-mapped "
        "codes become key lanes with zero searchsorted, dedup/partial-"
        "update/aggregation and sort-compact run on codes, and flush/"
        "compaction encode emits dictionary pages straight from the unified "
        "pool. Falls back to the expanded path per file/merge when a column "
        "is not dictionary-encoded or the domain exceeds "
        "merge.dict-domain.pool-limit. Output rows are bit-identical to the "
        "expanded path. PAIMON_TPU_DICT_DOMAIN overrides.",
    )
    MERGE_DICT_DOMAIN_POOL_LIMIT = ConfigOption.int_(
        "merge.dict-domain.pool-limit",
        1 << 20,
        "Largest dictionary domain (distinct values per column) the "
        "code-domain merge path will carry — a single file dictionary or a "
        "unified merge pool above this expands to strings instead "
        "(dict{fallback_expanded}). PAIMON_TPU_DICT_POOL_LIMIT overrides.",
    )
    JOIN_ALGORITHM = ConfigOption.string(
        "join.algorithm",
        "auto",
        "Equi-join kernel: 'hash' probes a sorted single-operand key by "
        "binary search, 'sort-merge' routes multi-operand keys through the "
        "merge kernel's sorted_segments seam (inheriting sort-engine=pallas), "
        "'auto' picks hash exactly when the global lane plan packed the key "
        "into one fused uint32 operand.",
    )
    JOIN_ENGINE = ConfigOption.string(
        "join.engine",
        "auto",
        "Join execution backend: 'numpy' (host lexsort/searchsorted), 'xla' "
        "or 'pallas' (device kernels). 'auto' mirrors the merge rule — host "
        "below join.device-rows or on a CPU-only platform, device otherwise, "
        "with the device flavor following sort-engine. "
        "PAIMON_TPU_JOIN_ENGINE overrides.",
    )
    JOIN_DEVICE_ROWS = ConfigOption.int_(
        "join.device-rows",
        4096,
        "Smallest combined row count (probe + build) the auto engine sends "
        "to the device kernels; smaller joins stay on the host where "
        "dispatch overhead dominates.",
    )
    JOIN_CHUNK_ROWS = ConfigOption.int_(
        "join.chunk-rows",
        1 << 20,
        "Probe rows per join partition: a probe side larger than this "
        "splits into ceil(rows / chunk) key-disjoint partitions (bounding "
        "device batch size), with heavy-hitter keys skew-split across all "
        "partitions (JSPIM). join.partitions overrides the count directly.",
    )
    JOIN_PARTITIONS = ConfigOption.int_(
        "join.partitions",
        0,
        "Explicit join partition count (0 = derive from join.chunk-rows). "
        "Values > 1 enable the skew-aware split even for small probes.",
    )
    JOIN_SKEW_FACTOR = ConfigOption.float_(
        "join.skew-factor",
        0.5,
        "A join key is a heavy hitter when it holds >= this fraction of "
        "the fair per-partition probe share (probe_rows / partitions) — a "
        "hot key cannot be subdivided by hashing, so it is dealt "
        "round-robin across every partition with its build rows "
        "replicated, and never serializes one partition (JSPIM).",
    )
    JOIN_PUSHDOWN_IN_LIMIT = ConfigOption.int_(
        "join.pushdown-in-limit",
        1024,
        "SELECT ... JOIN planning: when the smaller side's distinct join "
        "keys number at most this, the big side's scan is pruned with an "
        "IN predicate over those keys (file/row-group skipping); above it, "
        "a BETWEEN over the small side's key range is pushed instead.",
    )
    MERGE_EXEC_ENGINE = ConfigOption.string(
        "merge.engine",
        "single",
        "Merge EXECUTION engine (orthogonal to merge-engine, which picks the "
        "per-key semantics): 'single' runs each bucket's sort-merge as its "
        "own device call; 'mesh' routes scans, compaction rewrites and "
        "writer flushes through the mesh-sharded execution layer "
        "(parallel.mesh_exec.MeshExecutor) — per-bucket merges batch into "
        "one shard_map per merge-function family over the mesh's bucket "
        "axis with globally-agreed lane plans, oversized buckets "
        "range-shuffle over the key axis, and the split pipeline feeds one "
        "prefetch lane per device. Output is bit-identical to 'single'; a "
        "1-device or shard_map-less environment degrades to 'single' "
        "automatically (cpu fallback). PAIMON_TPU_MERGE_ENGINE overrides.",
    )
    DATA_FILE_INCLUDE_KEY_COLUMNS = ConfigOption.bool_(
        "data-file.include-key-columns",
        False,
        "Duplicate the trimmed primary key as _KEY_<name> columns at the "
        "front of every data file (the reference KeyValue.schema layout) — "
        "with manifest.format=avro this makes the whole table "
        "reference-layout on disk.",
    )
    SOURCE_SPLIT_TARGET_SIZE = ConfigOption.memory(
        "source.split.target-size", "128 mb", "Target size of one batch-read split."
    )
    SOURCE_SPLIT_OPEN_FILE_COST = ConfigOption.memory(
        "source.split.open-file-cost", "4 mb", "Weight floor per file when packing splits."
    )
    FS_RETRY_MAX_ATTEMPTS = ConfigOption.int_(
        "fs.retry.max-attempts",
        3,
        "Total tries per FileIO op before a transient fault becomes fatal "
        "(resilience.RetryingFileIO, installed by the store). 1 disables "
        "retrying entirely — the wrapper is then not even constructed.",
    )
    FS_RETRY_INITIAL_BACKOFF = ConfigOption.duration(
        "fs.retry.initial-backoff",
        "10 ms",
        "Base backoff between IO retries; actual sleeps use decorrelated "
        "jitter (U(base, 3*prev), capped by fs.retry.max-backoff).",
    )
    FS_RETRY_MAX_BACKOFF = ConfigOption.duration(
        "fs.retry.max-backoff", "2 s", "Cap on a single IO retry backoff."
    )
    FS_IO_TIMEOUT = ConfigOption.duration(
        "fs.io.timeout",
        None,
        "Per-op wall-clock deadline spanning all retry attempts; past it the "
        "op fails with IODeadlineExceeded (counted in io{timeouts}). Unset = "
        "unbounded.",
    )
    COMMIT_MAX_RETRIES = ConfigOption.int_(
        "commit.max-retries",
        10,
        "Bounded commit retry loop: snapshot-CAS races (and conflict "
        "re-plans) are retried this many times with commit.retry-backoff "
        "between rounds before the commit gives up (CommitGiveUpError). The "
        "seed looped forever — a livelock under heavy contention.",
    )
    COMMIT_RETRY_BACKOFF = ConfigOption.duration(
        "commit.retry-backoff",
        "10 ms",
        "Base backoff between commit retry rounds (decorrelated jitter, "
        "capped at 100x base) so racing committers desynchronize.",
    )
    SOAK_DURATION = ConfigOption.duration(
        "soak.duration",
        "45 s",
        "Traffic-soak harness (service.soak): how long the concurrent "
        "writer/reader/churn threads run before the final drain and orphan "
        "sweep.",
    )
    SOAK_WRITERS = ConfigOption.int_(
        "soak.writers", 3, "Traffic-soak harness: number of concurrent committer threads."
    )
    SOAK_READERS = ConfigOption.int_(
        "soak.readers",
        2,
        "Traffic-soak harness: number of concurrent snapshot-reader threads "
        "(each read is verified against the serialized oracle log).",
    )
    SOAK_FAULT_POSSIBILITY = ConfigOption.int_(
        "soak.fault.possibility",
        0,
        "Traffic-soak harness: inject a transient IO fault on 1/N of "
        "filesystem ops (0 = no faults; 20 = the 5% headline rate).",
    )
    SOAK_ROWS_PER_COMMIT = ConfigOption.int_(
        "soak.rows-per-commit", 400, "Traffic-soak harness: rows each writer commits per round."
    )
    SOAK_COMPACT_EVERY = ConfigOption.int_(
        "soak.compact-every",
        4,
        "Traffic-soak harness: every Nth commit of a writer forces a full "
        "compaction, driving the commit-conflict re-plan path on shared "
        "buckets.",
    )
    SOAK_PROCESS_DURATION = ConfigOption.duration(
        "soak.process.duration",
        "60 s",
        "Process-grain crash soak (service.proc_soak): how long the "
        "supervisor runs writer/reader OS processes (killing and respawning "
        "them) before the drain, oracle fold, and final sweep/audit.",
    )
    SOAK_PROCESS_WRITERS = ConfigOption.int_(
        "soak.process.writers",
        2,
        "Process-grain crash soak: number of concurrent writer OS processes "
        "(each with its own intent/ack journal, sharing only the warehouse "
        "filesystem).",
    )
    SOAK_PROCESS_READERS = ConfigOption.int_(
        "soak.process.readers",
        1,
        "Process-grain crash soak: number of reader OS processes pinning and "
        "scanning snapshots throughout the kill/respawn churn.",
    )
    SOAK_PROCESS_KILL_PERIOD = ConfigOption.duration(
        "soak.process.kill-period",
        "8 s",
        "Process-grain crash soak: mean interval between random SIGKILLs of "
        "writer processes (seeded; on top of the scripted "
        "PAIMON_TPU_CRASH_POINT kills). 0 = scripted kills only.",
    )
    SOAK_PROCESS_SWEEP_PERIOD = ConfigOption.duration(
        "soak.process.sweep-period",
        "12 s",
        "Process-grain crash soak: cadence of the supervisor's mid-soak "
        "orphan sweep (threshold soak.process kill debris older than ~45 s; "
        "a final sweep at threshold 0 runs after the drain regardless). "
        "0 = final sweep only.",
    )
    SOAK_MEGA_DURATION = ConfigOption.duration(
        "soak.mega.duration",
        "45 s",
        "Production mega-soak (service.mega_soak): how long each scenario "
        "cell runs its full process census (cluster mesh, gateway writers, "
        "getters, subscribers, SQL clients, churn threads) before the drain "
        "and the multi-plane oracle verdict.",
    )
    SOAK_MEGA_CLUSTER_WORKERS = ConfigOption.int_(
        "soak.mega.cluster-workers",
        2,
        "Production mega-soak: worker OS processes in the cluster plane of "
        "cells that enable it (mesh engine, adaptive compaction on).",
    )
    SOAK_MEGA_KILL_PERIOD = ConfigOption.duration(
        "soak.mega.kill-period",
        "9 s",
        "Production mega-soak: mean interval between seeded random SIGKILLs "
        "across all process kinds, on top of the scripted "
        "PAIMON_TPU_CRASH_POINT kill schedule. 0 = scripted kills only.",
    )
    SOAK_MEGA_CHAOS_READ = ConfigOption.float_(
        "soak.mega.chaos.read-ms",
        1.0,
        "Production mega-soak: mean injected read latency (ms) of the "
        "composed chaos store the whole warehouse lives on.",
    )
    SOAK_MEGA_CHAOS_WRITE = ConfigOption.float_(
        "soak.mega.chaos.write-ms",
        0.5,
        "Production mega-soak: mean injected write latency (ms) of the "
        "composed chaos store.",
    )
    SOAK_MEGA_CHAOS_POSSIBILITY = ConfigOption.int_(
        "soak.mega.chaos.possibility",
        200,
        "Production mega-soak: inject a transient IO fault on 1/N of "
        "filesystem ops across every plane (absorbed by the fs.retry "
        "budget; 0 = latency shaping only).",
    )
    CLUSTER_WORKERS = ConfigOption.int_(
        "cluster.workers",
        2,
        "Cluster service (service.cluster): number of worker OS processes "
        "the supervisor spawns. The coordinator splits the table's buckets "
        "into contiguous ranges, one per worker; each worker runs its local "
        "merge.engine=mesh executor over its shard and ships CommitMessages "
        "back — only the coordinator commits (the reference's "
        "single-parallelism committer).",
    )
    CLUSTER_DEVICES_PER_WORKER = ConfigOption.int_(
        "cluster.devices-per-worker",
        2,
        "Cluster service: virtual (forced-host) or real devices each worker "
        "process spans with its local mesh executor "
        "(--xla_force_host_platform_device_count in the spawned child).",
    )
    CLUSTER_HEARTBEAT_INTERVAL = ConfigOption.duration(
        "cluster.heartbeat-interval",
        "500 ms",
        "Cluster service: cadence of each worker's background heartbeat to "
        "the coordinator (also how it learns of assignment epoch changes).",
    )
    CLUSTER_HEARTBEAT_TIMEOUT = ConfigOption.duration(
        "cluster.heartbeat-timeout",
        "4 s",
        "Cluster service: a worker silent for this long is declared dead — "
        "its bucket range is reassigned (exactly once) to live workers, its "
        "in-flight debt-gate charges are released, and any CommitMessage it "
        "later ships for a reassigned bucket is rejected as stale.",
    )
    CLUSTER_ROUND_ROWS = ConfigOption.int_(
        "cluster.round-rows",
        256,
        "Cluster service soak/bench workers: rows per ingest round per "
        "owned bucket.",
    )
    CLUSTER_ADMIT_TIMEOUT = ConfigOption.duration(
        "cluster.admit-timeout",
        "30 s",
        "Cluster service: how long a worker keeps retrying the "
        "coordinator's debt-admission gate (read-amp ceiling enforced "
        "cluster-wide) before giving up on an ingest round.",
    )
    CLUSTER_COMPACTION_ENABLED = ConfigOption.bool_(
        "cluster.compaction.enabled",
        True,
        "Cluster service: run the coordinator-scheduled, worker-executed "
        "adaptive compaction drain (table.compactor policy deciding, the "
        "bucket's owning worker rewriting, the coordinator committing). "
        "Off = ingest only (read amplification unbounded).",
    )
    CLUSTER_RESCALE_TIMEOUT = ConfigOption.duration(
        "cluster.rescale.timeout",
        "120 s",
        "Elastic cluster: how long the coordinator waits for every owner's "
        "rescale rewrite shipment before abandoning the rescale (fence "
        "lifted, old bucket count kept, rewritten files left as orphans for "
        "the sweep). Worker deaths inside the window do not abort it — the "
        "reassignment machinery re-queues the dead owner's buckets on "
        "whoever inherits them.",
    )
    CLUSTER_REPLICA_HEAT_THRESHOLD = ConfigOption.float_(
        "cluster.replica.heat-threshold",
        0.0,
        "Elastic cluster: a bucket whose heat EMA (serve-side get rate plus "
        "the adaptive compactor's write-rate EMA, ops/s) crosses this gets "
        "a read replica on another live worker — the replica serves "
        "get_batch/subscribe/scan_frag off the shared-FS snapshot while the "
        "primary retains writes. 0 disables replica placement.",
    )
    CLUSTER_REPLICA_MAX_PER_BUCKET = ConfigOption.int_(
        "cluster.replica.max-per-bucket",
        1,
        "Elastic cluster: replica owners per hot bucket beyond the primary. "
        "Replicas decay back off when the bucket's heat EMA falls under "
        "half the threshold (hysteresis against flapping).",
    )
    CLUSTER_REPLICA_INTERVAL = ConfigOption.duration(
        "cluster.replica.interval",
        "1 s",
        "Elastic cluster: cadence of the coordinator's replica-placement "
        "pass (heat EMA refresh + promote/demote decisions). Every change "
        "bumps the route epoch so clients refresh immediately.",
    )
    SQL_CLUSTER_CODE_DOMAIN = ConfigOption.bool_(
        "sql.cluster.code-domain",
        True,
        "Distributed SQL (sql.cluster): ship GROUP BY keys coordinator-ward "
        "as (pruned dictionary pool, uint32 codes) and combine partials in "
        "the code domain via pool unification — no group key string ever "
        "expands on the wire or at the coordinator. Off = workers expand "
        "group key values and the coordinator re-encodes them. The "
        "PAIMON_TPU_SQL_CODE_DOMAIN env var overrides in either direction "
        "(the verify stage forces both paths).",
    )
    SQL_CLUSTER_SCAN_MAX_INFLIGHT = ConfigOption.int_(
        "sql.cluster.scan.max-inflight",
        4,
        "Distributed SQL: concurrent scan_frag fragments a worker serving "
        "plane executes before answering a typed BUSY (retry_after_ms) — "
        "a scan storm must not starve get_batch/subscribe serving. Shed "
        "fragments count into soak{shed_requests} beside every other "
        "serving-plane BUSY.",
    )
    SQL_CLUSTER_RETRY_TIMEOUT = ConfigOption.duration(
        "sql.cluster.retry-timeout",
        "30 s",
        "Distributed SQL: how long the coordinator keeps re-dispatching a "
        "query's unfinished fragments across route refreshes (worker "
        "deaths, reassignments, BUSY sheds) before the query fails.",
    )
    SQL_CLUSTER_FRAGMENT_CACHE = ConfigOption.bool_(
        "sql.cluster.fragment-cache",
        True,
        "Distributed SQL: cache aggregate fragment partials at the "
        "coordinator keyed on (snapshot id, bucket-layout epoch, fragment "
        "signature — semantic template plus every planned split). A "
        "repeated aggregate over an unchanged table answers without any "
        "worker RPC (sql{fragment_cache_hits}); a plan at a newer snapshot "
        "or under a rescaled bucket layout purges the table's stale "
        "entries.",
    )
    SQL_CLUSTER_SHUFFLE_THRESHOLD = ConfigOption.int_(
        "sql.cluster.shuffle.threshold",
        50_000,
        "Distributed SQL: estimated distinct-group count above which a "
        "GROUP BY combines via worker↔worker shuffle instead of at the "
        "coordinator. The estimate comes from the planned splits' file "
        "stats (integer key: global max-min+1; otherwise row count) at "
        "zero extra IO. The PAIMON_TPU_SQL_SHUFFLE env var forces the "
        "path on/off regardless of the estimate (the verify stage runs "
        "the parity suite both ways).",
    )
    SQL_CLUSTER_SHUFFLE_RANGES = ConfigOption.int_(
        "sql.cluster.shuffle.ranges",
        0,
        "Distributed SQL: number R of group-domain hash ranges a shuffle "
        "aggregation partitions into (each range owner unifies and "
        "reduces its range; the coordinator only concatenates). 0 = one "
        "range per live worker, the balanced default.",
    )
    GATEWAY_MAX_INFLIGHT = ConfigOption.int_(
        "gateway.max-inflight",
        64,
        "Multi-tenant gateway: default concurrent in-flight requests a "
        "tenant may hold before the gateway sheds with a typed "
        "'busy-inflight' ShedInfo (retry_after_ms hinted). Overridable "
        "per tenant via gateway.tenant.<id>.max-inflight.",
    )
    GATEWAY_BYTES_PER_SEC = ConfigOption.memory(
        "gateway.bytes-per-sec",
        "0 b",
        "Multi-tenant gateway: total request-byte budget per second shared "
        "weighted-fair across tenants (tenant i receives rate * w_i / sum "
        "of all configured weights, further capped by its own "
        "gateway.tenant.<id>.bytes-per-sec). 0 = unlimited; a tenant whose "
        "token bucket runs dry is shed with a typed 'throttling-bytes' "
        "ShedInfo whose retry_after_ms is the exact refill deadline.",
    )
    GATEWAY_TENANT_WEIGHT = ConfigOption.float_(
        "gateway.tenant.<id>.weight",
        1.0,
        "Multi-tenant gateway (templated key): tenant <id>'s weighted-fair "
        "share of gateway.bytes-per-sec. Untagged traffic lands in the "
        "'default' tenant with weight 1.0.",
    )
    GATEWAY_TENANT_MAX_INFLIGHT = ConfigOption.int_(
        "gateway.tenant.<id>.max-inflight",
        None,
        "Multi-tenant gateway (templated key): tenant <id>'s concurrent "
        "in-flight request cap, overriding gateway.max-inflight.",
    )
    GATEWAY_TENANT_BYTES_PER_SEC = ConfigOption.memory(
        "gateway.tenant.<id>.bytes-per-sec",
        "0 b",
        "Multi-tenant gateway (templated key): hard per-second byte cap for "
        "tenant <id>, applied on top of its weighted-fair share of the "
        "global gateway.bytes-per-sec budget. 0 = no per-tenant cap.",
    )
    GATEWAY_HEDGE_ENABLED = ConfigOption.bool_(
        "gateway.hedge.enabled",
        True,
        "Multi-tenant gateway: re-issue a point-get or scan fragment whose "
        "primary (owning worker) misses gateway.hedge.deadline-ms to a "
        "secondary live non-owner worker serving the same committed "
        "snapshot from the shared filesystem — first non-BUSY answer wins, "
        "the loser is cancelled and counted (gateway{hedges_cancelled}).",
    )
    GATEWAY_HEDGE_DEADLINE = ConfigOption.int_(
        "gateway.hedge.deadline-ms",
        50,
        "Multi-tenant gateway: milliseconds the primary worker gets before "
        "the gateway hedges the read to a secondary. Tail-latency armor — "
        "set near the healthy-path p99 so only stragglers pay the second "
        "RPC.",
    )
    GATEWAY_HEDGE_MAX_FRACTION = ConfigOption.float_(
        "gateway.hedge.max-fraction",
        0.25,
        "Multi-tenant gateway: upper bound on hedged requests as a fraction "
        "of all hedgeable requests — a cluster-wide brownout must not "
        "double every read. Beyond the bound the gateway waits out the "
        "primary instead of hedging.",
    )
    GATEWAY_SLO_DECAY_WINDOW = ConfigOption.duration(
        "gateway.slo.decay-window",
        "30 s",
        "Multi-tenant gateway: exponential-decay time constant of the SLO "
        "surface's latency histograms (gateway.slo() p50/p99 per tenant "
        "and request kind). Old samples fade with exp(-age/window) so the "
        "surface tracks current behavior, not the run's whole history.",
    )
    GATEWAY_RETRY_AFTER = ConfigOption.int_(
        "gateway.retry-after-ms",
        25,
        "Multi-tenant gateway: backoff hint stamped into inflight-cap sheds "
        "(byte-budget sheds compute their exact refill deadline instead).",
    )
    ORPHAN_CLEAN_OLDER_THAN = ConfigOption.duration(
        "orphan.clean.older-than",
        "1 d",
        "remove_orphan_files safety threshold: only files older than this "
        "are eligible for deletion (an in-flight commit's freshly written "
        "files must survive the sweep).",
    )
    COMMIT_CATALOG_LOCK = ConfigOption.bool_(
        "commit.catalog-lock.enabled",
        False,
        "Run snapshot commits under an external catalog lock (required on "
        "stores whose rename is not atomic; reference CatalogLock SPI).",
    )
    COMMIT_CATALOG_LOCK_TYPE = ConfigOption.string(
        "commit.catalog-lock.type",
        "file",
        "Catalog lock implementation: 'file' (lock object in the table dir; "
        "needs exclusive-create, i.e. conditional PUT on object stores) or "
        "'jdbc' (external lock database — the only safe choice on legacy "
        "object stores without conditional PUT).",
    )
    COMMIT_CATALOG_LOCK_JDBC_PATH = ConfigOption.string(
        "commit.catalog-lock.jdbc-path",
        None,
        "Database path for commit.catalog-lock.type=jdbc.",
    )
    COMMIT_CATALOG_LOCK_TIMEOUT = ConfigOption.float_(
        "commit.catalog-lock.acquire-timeout",
        60.0,
        "Seconds to wait for the catalog lock before the commit fails "
        "(reference catalog option lock-acquire-timeout).",
    )
    COMMIT_CATALOG_LOCK_STALE_TTL = ConfigOption.float_(
        "commit.catalog-lock.check-max-sleep",
        300.0,
        "Seconds after which a non-heartbeating lock holder is presumed "
        "crashed and its lock is swept (reference lock-check-max-sleep).",
    )
    PARALLEL_KEY_AXIS_ROWS = ConfigOption.int_(
        "parallel.key-axis.rows",
        4 * 1024 * 1024,
        "Row threshold above which one bucket's merge is range-partitioned "
        "over the mesh's key axis instead of running on a single device.",
    )
    CHANGELOG_NUM_RETAINED_MIN = ConfigOption.int_(
        "changelog.num-retained.min", None, "Min decoupled changelogs retained (enables the decoupled lifecycle)."
    )
    CHANGELOG_NUM_RETAINED_MAX = ConfigOption.int_(
        "changelog.num-retained.max", None, "Max decoupled changelogs retained."
    )
    CHANGELOG_TIME_RETAINED = ConfigOption.duration(
        "changelog.time-retained", None, "Decoupled changelog retention time (enables the decoupled lifecycle)."
    )
    CHANGELOG_PRODUCER_ROW_DEDUPLICATE = ConfigOption.bool_(
        "changelog-producer.row-deduplicate",
        True,
        "Drop -U/+U changelog pairs whose values did not change "
        "(full-compaction/lookup producers). Default true here: the diff is "
        "a vectorized compare, effectively free (reference defaults false "
        "because its row-by-row compare costs).",
    )
    DELETE_FORCE_PRODUCE_CHANGELOG = ConfigOption.bool_(
        "delete.force-produce-changelog",
        False,
        "DELETE/UPDATE commands produce input changelog even when "
        "changelog-producer=none.",
    )
    STREAMING_READ_OVERWRITE = ConfigOption.bool_(
        "streaming-read-overwrite",
        False,
        "Streaming reads also emit the new content of OVERWRITE snapshots.",
    )
    STREAMING_READ_MODE = ConfigOption.string(
        "streaming-read-mode", "file", "Streaming source: file (lake files). 'log' needs an external log system."
    )
    STREAM_SCAN_MODE = ConfigOption.string(
        "stream-scan-mode",
        "none",
        "none: normal changelog-aware follow-up; file-monitor: raw delta "
        "files of EVERY snapshot incl. compaction (compactor sources).",
    )
    CONTINUOUS_DISCOVERY_INTERVAL = ConfigOption.duration(
        "continuous.discovery-interval", "10 s", "Poll interval for discovering new snapshots in streaming reads."
    )
    CONSUMER_IGNORE_PROGRESS = ConfigOption.bool_(
        "consumer.ignore-progress", False, "Start from the startup mode, ignoring saved consumer progress."
    )
    SUBSCRIPTION_QUEUE_DEPTH = ConfigOption.int_(
        "subscription.queue-depth",
        16,
        "CDC subscription service: max decoded changelog batches buffered "
        "per subscriber. A queue full past subscription.shed-timeout sheds "
        "that subscriber (typed BUSY) — it never stalls the tailer.",
    )
    SUBSCRIPTION_POLL_BACKOFF = ConfigOption.duration(
        "subscription.poll-backoff",
        "20 ms",
        "CDC subscription service: initial tailer backoff when no new "
        "snapshot is available, doubling up to "
        "continuous.discovery-interval (blocking poll, no busy loop).",
    )
    SUBSCRIPTION_SHED_TIMEOUT = ConfigOption.duration(
        "subscription.shed-timeout",
        "2 s",
        "CDC subscription service: how long the tailer waits on one "
        "subscriber's full queue (or the shared buffer budget) before "
        "shedding that subscriber with a typed SubscriberShedError carrying "
        "its durable restart offset.",
    )
    SUBSCRIPTION_HEARTBEAT_INTERVAL = ConfigOption.duration(
        "subscription.heartbeat-interval",
        "5 s",
        "CDC subscription service: cadence of durable consumer-position "
        "re-records. Each record refreshes the consumer file's mtime, so "
        "consumer.expiration-time only collects readers that stopped "
        "heartbeating.",
    )
    SUBSCRIPTION_MAX_SUBSCRIBERS = ConfigOption.int_(
        "subscription.max-subscribers",
        1024,
        "CDC subscription service: subscriber cap per table hub; subscribe() "
        "past it answers a typed BUSY immediately.",
    )
    SUBSCRIPTION_REPLAY_CACHE_MAX_MEMORY = ConfigOption.memory(
        "subscription.replay-cache.max-memory",
        "32 mb",
        "CDC subscription service: byte budget for the hub's replay cache of "
        "decoded ChangelogBatches (LRU by snapshot). The data-file cache "
        "already makes PAGE decode once-per-process; this extends decode-once "
        "to the merged batch, so catch-up replay and shed-resume reuse the "
        "tailer's decode+merge instead of re-merging per subscriber. "
        "0 b = off.",
    )
    SUBSCRIPTION_BUFFER_MAX_MEMORY = ConfigOption.memory(
        "subscription.buffer.max-memory",
        "64 mb",
        "CDC subscription service: shared byte budget for queued decoded "
        "batches across ALL subscribers of a table (the PR 8 "
        "WriteBufferController riding the fan-out path). 0 b = unbounded.",
    )
    CONSUMER_MODE = ConfigOption.string(
        "consumer.mode",
        "exactly-once",
        "exactly-once: progress advances on checkpoint ack; at-least-once: on every plan.",
    )
    BRANCH = ConfigOption.string("branch", "main", "Branch this table view reads and writes.")
    CHANGELOG_PRODUCER = ConfigOption.enum(
        "changelog-producer", ChangelogProducer, ChangelogProducer.NONE, "How changelog files are produced."
    )
    SCAN_MODE = ConfigOption.enum(
        "scan.mode", StartupMode, StartupMode.DEFAULT, "Startup mode for scans.", fallback=("log.scan",)
    )
    SCAN_SNAPSHOT_ID = ConfigOption.int_("scan.snapshot-id", None, "Snapshot id for time travel.")
    SCAN_TIMESTAMP_MILLIS = ConfigOption.int_(
        "scan.timestamp-millis", None, "Timestamp for time travel.", fallback=("log.scan.timestamp-millis",)
    )
    SCAN_TIMESTAMP = ConfigOption.string(
        "scan.timestamp", None, "Timestamp for time travel as 'YYYY-MM-DD[ HH:MM:SS[.ffffff]]' (local time)."
    )
    SCAN_TAG_NAME = ConfigOption.string("scan.tag-name", None, "Tag name for time travel.")
    SCAN_VERSION = ConfigOption.string(
        "scan.version", None, "Unified time travel: a tag name, or a snapshot id (tag wins on ambiguity)."
    )
    SCAN_WATERMARK = ConfigOption.int_(
        "scan.watermark", None, "Travel to the earliest snapshot whose watermark is >= this value."
    )
    SCAN_FILE_CREATION_TIME_MILLIS = ConfigOption.int_(
        "scan.file-creation-time-millis", None, "Only read data files created after this epoch-millis."
    )
    SCAN_PLAN_SORT_PARTITION = ConfigOption.bool_(
        "scan.plan-sort-partition",
        False,
        "true: splits strictly partition-major (sorted sequential consumption); "
        "false: round-robin across partitions (spreads parallel readers).",
    )
    SCAN_MAX_SPLITS_PER_TASK = ConfigOption.int_(
        "scan.max-splits-per-task", 10, "Split-assignment batch cap per reader task in the enumerator."
    )
    SCAN_MANIFEST_PARALLELISM = ConfigOption.int_(
        "scan.manifest.parallelism", None, "Threads for reading manifests during scan planning (default: scan.parallelism)."
    )
    SCAN_PREFETCH_SPLITS = ConfigOption.int_(
        "scan.prefetch-splits",
        2,
        "Readahead depth of the pipelined split scheduler: how many splits/"
        "compaction sections/flush encodes may run ahead of the consumer. "
        "0 disables pipelining everywhere (strictly sequential execution; "
        "output is bit-identical either way).",
    )
    SCAN_PARALLELISM = ConfigOption.int_(
        "scan.parallelism",
        None,
        "Worker threads per pipeline stage, and the in-flight bound of the "
        "per-file/manifest decode fan-out (default: min(prefetch+1, 4) for "
        "stages, shared-pool width for decode fan-out).",
    )
    INCREMENTAL_BETWEEN_TIMESTAMP = ConfigOption.string(
        "incremental-between-timestamp",
        None,
        "Incremental read between two epoch-millis timestamps 't1,t2' (resolved to snapshots).",
    )
    INCREMENTAL_BETWEEN = ConfigOption.string(
        "incremental-between",
        None,
        "Read incremental changes between two snapshots or tags "
        "('3,7' or 'tagA,tagB'): start exclusive, end inclusive.",
    )
    INCREMENTAL_BETWEEN_SCAN_MODE = ConfigOption.string(
        "incremental-between-scan-mode",
        "delta",
        "Incremental read source: delta (APPEND snapshot deltas) or "
        "changelog (changelog files of the range).",
    )
    SCAN_BOUNDED_WATERMARK = ConfigOption.int_(
        "scan.bounded.watermark",
        None,
        "Streaming reads end once a snapshot's watermark passes this bound.",
    )
    SNAPSHOT_EXPIRE_LIMIT = ConfigOption.int_(
        "snapshot.expire.limit", 50, "Max snapshots processed per expire run."
    )
    SNAPSHOT_EXPIRE_CLEAN_EMPTY_DIRS = ConfigOption.bool_(
        "snapshot.expire.clean-empty-directories",
        False,
        "Also remove bucket/partition directories left empty by expiry.",
    )
    SNAPSHOT_NUM_RETAINED_MIN = ConfigOption.int_("snapshot.num-retained.min", 10, "Min snapshots retained.")
    SNAPSHOT_NUM_RETAINED_MAX = ConfigOption.int_("snapshot.num-retained.max", 2147483647, "Max snapshots retained.")
    SNAPSHOT_TIME_RETAINED_MS = ConfigOption.duration(
        "snapshot.time-retained", "1 h", "Snapshot retention time.", fallback=("snapshot.time-retained.ms",)
    )
    NUM_SORTED_RUNS_COMPACTION_TRIGGER = ConfigOption.int_(
        "num-sorted-run.compaction-trigger", 5, "Sorted runs per bucket that trigger compaction."
    )
    NUM_SORTED_RUNS_STOP_TRIGGER = ConfigOption.int_(
        "num-sorted-run.stop-trigger", None, "Sorted runs that block writes (default trigger+3)."
    )
    NUM_LEVELS = ConfigOption.int_("num-levels", None, "LSM levels (default trigger+1).")
    COMPACTION_MAX_SIZE_AMP_PERCENT = ConfigOption.int_(
        "compaction.max-size-amplification-percent", 200, "Universal compaction size-amp trigger."
    )
    COMPACTION_SIZE_RATIO = ConfigOption.int_("compaction.size-ratio", 1, "Universal compaction size ratio percent.")
    COMPACTION_MIN_FILE_NUM = ConfigOption.int_("compaction.min.file-num", 5, "Min files for size-ratio pick.")
    COMPACTION_MAX_FILE_NUM = ConfigOption.int_(
        "compaction.max.file-num",
        50,
        "Cap on files merged by one size-ratio/file-num pick (bounds a "
        "single compaction's input; reference compaction.max.file-num).",
        fallback=("compaction.early-max.file-num",),
    )
    COMPACTION_OPTIMIZATION_INTERVAL = ConfigOption.int_(
        "compaction.optimization-interval", None, "Force full compaction every N millis."
    )
    FULL_COMPACTION_DELTA_COMMITS = ConfigOption.int_(
        "full-compaction.delta-commits", None, "Full compaction every N commits."
    )
    COMPACTION_ADAPTIVE_ENABLED = ConfigOption.bool_(
        "compaction.adaptive.enabled",
        False,
        "Drain compaction debt through the LUDA-style adaptive background "
        "scheduler (table.compactor.AdaptiveCompactorService) instead of "
        "inline with writers: hot buckets compact deeper and earlier, cold "
        "ones defer, and per-bucket read amplification stays under "
        "compaction.adaptive.read-amp-ceiling. Ingest writers typically run "
        "write-only alongside it.",
    )
    COMPACTION_ADAPTIVE_INTERVAL = ConfigOption.duration(
        "compaction.adaptive.interval",
        "200 ms",
        "Pause between adaptive-scheduler observation rounds (each round "
        "scans the latest snapshot's per-bucket LSM shape and compacts the "
        "buckets the policy picks).",
    )
    COMPACTION_ADAPTIVE_READ_AMP_CEILING = ConfigOption.int_(
        "compaction.adaptive.read-amp-ceiling",
        12,
        "Per-bucket sorted-run ceiling: a bucket at or above it is compacted "
        "with mandatory priority regardless of heat, bounding merge-read "
        "amplification under sustained ingest.",
    )
    COMPACTION_ADAPTIVE_TRIGGER = ConfigOption.int_(
        "compaction.adaptive.trigger",
        3,
        "Sorted runs before a bucket becomes eligible for proactive adaptive "
        "compaction; below it the bucket is deferred (counted in "
        "compaction{deferred_buckets}).",
    )
    COMPACTION_ADAPTIVE_MAX_BUCKETS = ConfigOption.int_(
        "compaction.adaptive.max-buckets-per-round",
        2,
        "Proactive buckets compacted per scheduler round — bounds the "
        "background work one round can steal from ingest (ceiling breaches "
        "are exempt: the read-amp bound always wins).",
    )
    COMPACTION_ADAPTIVE_DEEP_RUNS = ConfigOption.int_(
        "compaction.adaptive.deep-runs",
        8,
        "Sorted runs at or above which an adaptive compaction goes deep "
        "(full rewrite to the top level) instead of a shallow universal "
        "pick — LUDA's compact-hotter-buckets-deeper rule.",
    )
    COMPACTION_ADAPTIVE_PARALLELISM = ConfigOption.int_(
        "compaction.adaptive.parallelism",
        2,
        "Worker threads executing the adaptive scheduler's per-bucket "
        "compactions concurrently (distinct buckets commit independently "
        "through the snapshot CAS; LUDA's premise is that compaction is "
        "cheap enough to run ahead of demand — parallel workers are how "
        "the drain rate scales past one bucket at a time).",
    )
    COMPACTION_ADAPTIVE_INGEST_GATE = ConfigOption.bool_(
        "compaction.adaptive.ingest-gate",
        True,
        "Bound write-only ingest by the adaptive scheduler's debt-admission "
        "gate: when an AdaptiveCompactorService is running for the table, "
        "every MergeTreeWriter flush first admits against the read-amp "
        "ceiling (blocking while the target bucket's projected sorted-run "
        "count sits at/over it, up to "
        "compaction.adaptive.ingest-gate-timeout) and settles its one-run "
        "charge when the flush lands — so ANY write-only writer is "
        "read-amp-bounded, not just harnesses that call admit() by hand.",
    )
    COMPACTION_ADAPTIVE_INGEST_GATE_TIMEOUT = ConfigOption.duration(
        "compaction.adaptive.ingest-gate-timeout",
        "30 s",
        "Longest a gated write-only flush blocks waiting for compaction "
        "headroom; on timeout the flush proceeds (the breach is the "
        "scheduler's to drain) — the gate bounds read amplification, it "
        "must never wedge ingest on a stalled compactor.",
    )
    COMPACTION_ADAPTIVE_STARVATION_TIMEOUT = ConfigOption.duration(
        "compaction.adaptive.starvation-timeout",
        "10 s",
        "A bucket whose compaction debt has been deferred longer than this "
        "is promoted to mandatory priority — cold buckets cannot starve "
        "under sustained skewed writes.",
    )
    DYNAMIC_BUCKET_TARGET_ROW_NUM = ConfigOption.int_(
        "dynamic-bucket.target-row-num", 2_000_000, "Rows per dynamic bucket."
    )
    DELETION_VECTORS_ENABLED = ConfigOption.bool_("deletion-vectors.enabled", False, "Deletion-vector mode.")
    SEQUENCE_FIELD = ConfigOption.string("sequence.field", None, "User-defined sequence column(s).")
    PARTIAL_UPDATE_REMOVE_RECORD_ON_DELETE = ConfigOption.bool_(
        "partial-update.remove-record-on-delete", False, "-D removes whole row under partial-update."
    )
    AGGREGATE_DEFAULT_FUNC = ConfigOption.string(
        "fields.default-aggregate-function", None, "Default aggregate for unconfigured fields."
    )
    WRITE_MAX_WRITERS_TO_SPILL = ConfigOption.int_("write-max-writers-to-spill", 5, "Writers before spill.")
    SORT_SPILL_THRESHOLD = ConfigOption.int_("sort-spill-threshold", None, "Merge fan-in before spill.")
    # tiles keep one merge step within device memory; per-dispatch latency
    # makes small tiles counterproductive, so the default only kicks in for
    # genuinely large sections
    MERGE_READ_BATCH_ROWS = ConfigOption.int_(
        "merge.read-batch-rows", 8 << 20, "Row tile per device merge step (key-range tiling)."
    )
    CONSUMER_ID = ConfigOption.string("consumer-id", None, "Consumer id protecting read progress.")
    CONSUMER_EXPIRATION_TIME_MS = ConfigOption.duration(
        "consumer.expiration-time", None, "Consumer expiry.", fallback=("consumer.expiration-time.ms",)
    )
    TAG_AUTOMATIC_CREATION = ConfigOption.string("tag.automatic-creation", "none", "none|process-time|watermark.")
    TAG_CREATION_DELAY = ConfigOption.duration(
        "tag.creation-delay", "0 ms", "Extra wait after a period closes before its tag is created."
    )
    TAG_PERIOD_FORMATTER = ConfigOption.string(
        "tag.period-formatter", "with_dashes", "Tag name style: with_dashes (2024-01-02[ 03]) | without_dashes (20240102[03])."
    )
    TAG_NUM_RETAINED_MAX = ConfigOption.int_(
        "tag.num-retained-max", None, "Max auto-created tags kept (oldest pruned first)."
    )
    TAG_DEFAULT_TIME_RETAINED = ConfigOption.duration(
        "tag.default-time-retained", None, "Auto tags older than this (by tagged snapshot time) are removed."
    )
    TAG_CALLBACKS = ConfigOption.string(
        "tag.callbacks", None, "Comma list of 'module:function' callables invoked as fn(table, tag_name, snapshot)."
    )
    COMMIT_CALLBACKS = ConfigOption.string(
        "commit.callbacks", None, "Comma list of 'module:function' callables invoked as fn(table, snapshot) after commit."
    )
    COMMIT_USER_PREFIX = ConfigOption.string(
        "commit.user-prefix", None, "Generated commit users become '<prefix>-<uuid>' (job attribution)."
    )
    COMMIT_FORCE_COMPACT = ConfigOption.bool_(
        "commit.force-compact", False, "Run a full compaction as part of every batch prepare_commit."
    )
    COMMIT_FORCE_CREATE_SNAPSHOT = ConfigOption.bool_(
        "commit.force-create-snapshot", False, "Create a snapshot even for an empty commit."
    )
    DYNAMIC_PARTITION_OVERWRITE = ConfigOption.bool_(
        "dynamic-partition-overwrite",
        True,
        "INSERT OVERWRITE without a partition filter clears only the "
        "partitions present in the new data (false: whole table).",
    )
    ROWKIND_FIELD = ConfigOption.string(
        "rowkind.field", None, "Column holding the row kind ('+I'/'-U'/'+U'/'-D') extracted on write."
    )
    PARTITION_DEFAULT_NAME = ConfigOption.string(
        "partition.default-name", "__DEFAULT_PARTITION__", "Path name used for null/empty partition values."
    )
    TAG_CREATION_PERIOD = ConfigOption.string("tag.creation-period", "daily", "daily|hourly.")
    METADATA_STATS_MODE = ConfigOption.string("metadata.stats-mode", "truncate(16)", "Stats collection mode.")
    MANIFEST_TARGET_SIZE = ConfigOption.memory("manifest.target-file-size", "8 mb", "Manifest merge target size.")
    MANIFEST_MERGE_MIN_COUNT = ConfigOption.int_("manifest.merge-min-count", 30, "Small manifests before merge.")
    PARTITION_EXPIRATION_TIME_MS = ConfigOption.duration(
        "partition.expiration-time", None, "Partition TTL.", fallback=("partition.expiration-time.ms",)
    )
    PARTITION_EXPIRATION_CHECK_INTERVAL = ConfigOption.duration(
        "partition.expiration-check-interval", "1 h",
        "Min interval between partition-expiry sweeps piggybacked on commits.",
    )
    PARTITION_TIMESTAMP_FORMATTER = ConfigOption.string("partition.timestamp-formatter", None)
    PARTITION_TIMESTAMP_PATTERN = ConfigOption.string("partition.timestamp-pattern", None)
    RECORD_LEVEL_EXPIRE_TIME_MS = ConfigOption.duration(
        "record-level.expire-time", None, "Row TTL on read/compact.", fallback=("record-level.expire-time.ms",)
    )
    RECORD_LEVEL_TIME_FIELD = ConfigOption.string("record-level.time-field", None, "Row TTL time column.")
    RECORD_LEVEL_TIME_FIELD_TYPE = ConfigOption.string(
        "record-level.time-field-type", "seconds", "Row TTL column unit: seconds|millis|micros."
    )
    FILE_INDEX_BLOOM_COLUMNS = ConfigOption.string(
        "file-index.bloom-filter.columns", None, "Columns with bloom file index."
    )
    FILE_INDEX_BLOOM_FPP = ConfigOption.float_("file-index.bloom-filter.fpp", 0.05, "Bloom false-positive rate.")
    FILE_INDEX_READ_ENABLED = ConfigOption.bool_(
        "file-index.read.enabled", True, "Evaluate file index (bloom sidecars / embedded) during planning."
    )
    FILE_INDEX_BLOOM_KEY_ENABLED = ConfigOption.bool_(
        "file-index.bloom-filter.primary-key.enabled", False,
        "Primary-key tables: write a composite key bloom (one __KEY__ entry "
        "over the combined key-column hash) into every data file's PTIX "
        "index at flush/compaction time, so batched point-get planning can "
        "prune files with zero data IO. PAIMON_TPU_KEY_BLOOM=1/0 overrides.",
    )
    FILE_INDEX_BLOOM_KEY_FPP = ConfigOption.float_(
        "file-index.bloom-filter.primary-key.fpp", 0.001,
        "Key bloom false-positive rate. Tighter than the per-column default "
        "because a batched get probes MANY keys per file: the per-file "
        "false-positive budget must survive the union over the batch.",
    )
    FILE_INDEX_IN_MANIFEST_THRESHOLD = ConfigOption.memory(
        "file-index.in-manifest-threshold",
        "500 b",
        "Index payloads smaller than this embed in the manifest entry "
        "instead of a sidecar file (saves one open per file per scan).",
    )
    AUTO_CREATE = ConfigOption.bool_(
        "auto-create", False, "Create the underlying table storage on first load when a schema is supplied."
    )
    PRIMARY_KEY = ConfigOption.string(
        "primary-key", None,
        "Define the primary key via options (comma-separated) when the "
        "creating surface cannot express constraints (reference: cannot be "
        "combined with an explicit primary key).",
    )
    PARTITION = ConfigOption.string(
        "partition", None, "Define partition keys via options (comma-separated); same contract as primary-key."
    )
    CHANGELOG_PRODUCER_LOOKUP_WAIT = ConfigOption.bool_(
        "changelog-producer.lookup-wait",
        True,
        "changelog-producer=lookup: commit waits for the lookup compaction "
        "(false: defer changelog production to a later compaction).",
    )
    SNAPSHOT_EXPIRE_EXECUTION_MODE = ConfigOption.string(
        "snapshot.expire.execution-mode", "sync", "sync | async (expire runs on a background thread)."
    )
    SNAPSHOT_WATERMARK_IDLE_TIMEOUT = ConfigOption.duration(
        "snapshot.watermark-idle-timeout",
        None,
        "Streaming reads: advance the watermark to the snapshot commit time "
        "when no new snapshot arrived for this long.",
    )
    DYNAMIC_BUCKET_INITIAL_BUCKETS = ConfigOption.int_(
        "dynamic-bucket.initial-buckets", None, "Dynamic bucket mode: buckets pre-created per assigner."
    )
    DYNAMIC_BUCKET_ASSIGNER_PARALLELISM = ConfigOption.int_(
        "dynamic-bucket.assigner-parallelism", None,
        "Dynamic bucket mode: assigner operators; new buckets are striped "
        "bucket %% parallelism == assigner_id (default: writer parallelism).",
    )
    CROSS_PARTITION_UPSERT_BOOTSTRAP_PARALLELISM = ConfigOption.int_(
        "cross-partition-upsert.bootstrap-parallelism", 10,
        "Threads reading existing keys when bootstrapping the cross-partition index.",
    )
    CROSS_PARTITION_UPSERT_INDEX_TTL = ConfigOption.duration(
        "cross-partition-upsert.index-ttl", None,
        "TTL for rows in the cross-partition key->(partition,bucket) index "
        "(0/None = keep forever; shorter = less memory, risk of stale rows).",
    )
    DELETION_VECTOR_INDEX_FILE_TARGET_SIZE = ConfigOption.memory(
        "deletion-vector.index-file.target-size", "2 mb",
        "Roll the packed deletion-vector container at this size.",
    )
    CACHE_MANIFEST_MAX_MEMORY = ConfigOption.memory(
        "cache.manifest.max-memory-size",
        "256 mb",
        "Byte budget of the process-wide decoded manifest/metadata object "
        "cache (manifest entry lists, manifest-list metas, snapshots, the "
        "latest-snapshot pointer). '0 b' opts this table out.",
    )
    CACHE_DATA_FILE_MAX_MEMORY = ConfigOption.memory(
        "cache.data-file.max-memory-size",
        "128 mb",
        "Byte budget of the process-wide decoded data-file (KVBatch) cache "
        "over predicate-free reader_factory reads. '0 b' opts this table out.",
    )
    LOOKUP_CACHE_MAX_MEMORY_SIZE = ConfigOption.memory(
        "lookup.cache-max-memory-size", "256 mb", "Lookup in-memory cache byte budget."
    )
    LOOKUP_CACHE_MAX_DISK_SIZE = ConfigOption.memory(
        "lookup.cache-max-disk-size", f"{1 << 50} b",
        "Lookup on-disk cache byte budget (oldest persisted lookup files evicted first).",
    )
    LOOKUP_CACHE_FILE_RETENTION = ConfigOption.duration(
        "lookup.cache-file-retention", "1 h", "Persisted lookup files older than this are re-buildable garbage."
    )
    LOOKUP_CACHE_BLOOM_FILTER_ENABLED = ConfigOption.bool_(
        "lookup.cache.bloom.filter.enabled", True, "Guard lookup files with a bloom filter of their keys."
    )
    LOOKUP_CACHE_BLOOM_FILTER_FPP = ConfigOption.float_(
        "lookup.cache.bloom.filter.fpp", 0.05, "Lookup bloom filter false-positive rate."
    )
    LOOKUP_HASH_LOAD_FACTOR = ConfigOption.float_(
        "lookup.hash-load-factor", 0.75, "Fill ratio of the sorted-hash lookup sidecar's slot table."
    )
    LOOKUP_GET_BLOOM_PRUNE = ConfigOption.bool_(
        "lookup.get.bloom-prune.enabled", True,
        "Batched gets consult per-file key blooms (and key ranges) to prune "
        "files before any data IO. Off = every candidate file is probed.",
    )
    LOOKUP_GET_MAX_INFLIGHT = ConfigOption.int_(
        "lookup.get.max-inflight", 64,
        "Concurrent get_batch requests a serving endpoint (KV server / "
        "Flight do_action) admits before answering a typed BUSY instead of "
        "queueing into a timeout.",
    )
    MANIFEST_FULL_COMPACTION_THRESHOLD_SIZE = ConfigOption.memory(
        "manifest.full-compaction-threshold-size", "16 mb",
        "Rewrite ALL manifests into compacted base manifests once the "
        "unmerged (delta) manifests exceed this total size.",
    )
    SORT_COMPACTION_RANGE_STRATEGY = ConfigOption.string(
        "sort-compaction.range-strategy", "quantity",
        "quantity: range-split sort compaction by row count; size: by bytes "
        "(skewed row widths pack ranges evenly).",
    )
    SORT_COMPACTION_SAMPLE_MAGNIFICATION = ConfigOption.int_(
        "sort-compaction.local-sample.magnification", 1000,
        "Local sample size = magnification x parallelism when choosing range boundaries.",
    )
    WRITE_BUFFER_FOR_APPEND = ConfigOption.bool_(
        "write-buffer-for-append", False,
        "Append tables: buffer rows (with spill) instead of flushing a file per write call.",
    )
    WRITE_BUFFER_SPILL_MAX_DISK_SIZE = ConfigOption.memory(
        "write-buffer-spill.max-disk-size", f"{1 << 50} b",
        "Cap on bytes of spill segments on local disk; past it the buffer flushes instead of spilling.",
    )
    ZORDER_VAR_LENGTH_CONTRIBUTION = ConfigOption.int_(
        "zorder.var-length-contribution", 8,
        "Bytes a var-length column (string/bytes) contributes to the z-order interleave.",
    )
    FIELDS_PREFIX = "fields."  # fields.<name>.aggregate-function / .sequence-group / .ignore-retract

    def __init__(self, options: Options | Mapping[str, Any] | None = None):
        self.options = options if isinstance(options, Options) else Options(options)

    # typed views ---------------------------------------------------------
    @property
    def bucket(self) -> int:
        return self.options.get(CoreOptions.BUCKET)

    @property
    def bucket_mode_hint(self) -> BucketMode:
        return BucketMode.FIXED if self.bucket > 0 else BucketMode.DYNAMIC

    @property
    def file_format(self) -> str:
        return self.options.get(CoreOptions.FILE_FORMAT)

    @property
    def file_compression(self) -> str:
        return self.options.get(CoreOptions.FILE_COMPRESSION)

    @property
    def merge_engine(self) -> MergeEngine:
        return self.options.get(CoreOptions.MERGE_ENGINE)

    @property
    def sort_engine(self) -> SortEngine:
        return self.options.get(CoreOptions.SORT_ENGINE)

    @property
    def lane_compression(self) -> bool:
        return self.options.get(CoreOptions.MERGE_LANE_COMPRESSION)

    @property
    def dict_domain(self) -> bool:
        return self.options.get(CoreOptions.MERGE_DICT_DOMAIN)

    @property
    def dict_domain_pool_limit(self) -> int:
        return self.options.get(CoreOptions.MERGE_DICT_DOMAIN_POOL_LIMIT)

    @property
    def changelog_producer(self) -> ChangelogProducer:
        return self.options.get(CoreOptions.CHANGELOG_PRODUCER)

    @property
    def target_file_size(self) -> int:
        return int(self.options.get(CoreOptions.TARGET_FILE_SIZE))

    @property
    def write_buffer_rows(self) -> int:
        return self.options.get(CoreOptions.WRITE_BUFFER_ROWS)

    @property
    def write_buffer_size(self) -> int:
        return int(self.options.get(CoreOptions.WRITE_BUFFER_SIZE))

    @property
    def write_buffer_max_memory(self) -> int:
        return int(self.options.get(CoreOptions.WRITE_BUFFER_MAX_MEMORY))

    @property
    def write_buffer_block_timeout_ms(self) -> int:
        return self.options.get(CoreOptions.WRITE_BUFFER_BLOCK_TIMEOUT)

    @property
    def write_only(self) -> bool:
        return self.options.get(CoreOptions.WRITE_ONLY)

    @property
    def num_sorted_runs_compaction_trigger(self) -> int:
        return self.options.get(CoreOptions.NUM_SORTED_RUNS_COMPACTION_TRIGGER)

    @property
    def num_sorted_runs_stop_trigger(self) -> int:
        v = self.options.get(CoreOptions.NUM_SORTED_RUNS_STOP_TRIGGER)
        return v if v is not None else self.num_sorted_runs_compaction_trigger + 3

    @property
    def num_levels(self) -> int:
        v = self.options.get(CoreOptions.NUM_LEVELS)
        return v if v is not None else self.num_sorted_runs_compaction_trigger + 1

    @property
    def max_size_amplification_percent(self) -> int:
        return self.options.get(CoreOptions.COMPACTION_MAX_SIZE_AMP_PERCENT)

    @property
    def size_ratio(self) -> int:
        return self.options.get(CoreOptions.COMPACTION_SIZE_RATIO)

    @property
    def compaction_min_file_num(self) -> int:
        return self.options.get(CoreOptions.COMPACTION_MIN_FILE_NUM)

    @property
    def snapshot_num_retained_min(self) -> int:
        return self.options.get(CoreOptions.SNAPSHOT_NUM_RETAINED_MIN)

    @property
    def snapshot_num_retained_max(self) -> int:
        return self.options.get(CoreOptions.SNAPSHOT_NUM_RETAINED_MAX)

    @property
    def snapshot_time_retained_ms(self) -> int:
        return self.options.get(CoreOptions.SNAPSHOT_TIME_RETAINED_MS)

    @property
    def sequence_field(self) -> list[str]:
        v = self.options.get(CoreOptions.SEQUENCE_FIELD)
        return [s.strip() for s in v.split(",")] if v else []

    @property
    def ignore_delete(self) -> bool:
        return self.options.get(CoreOptions.IGNORE_DELETE)

    def field_option(self, field_name: str, suffix: str) -> str | None:
        key = f"fields.{field_name}.{suffix}"
        return self.options._data.get(key)

    def to_map(self) -> dict[str, str]:
        return self.options.to_map()
