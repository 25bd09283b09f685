"""Small shared utilities (naming, paths, json, shared decode pool)."""

from __future__ import annotations

import json
import threading
import time
import uuid
from typing import Any, Sequence

__all__ = [
    "new_file_name",
    "partition_path",
    "now_millis",
    "dumps",
    "loads",
    "enable_compile_cache",
    "require_device",
    "shared_executor",
    "on_shared_pool",
]


_SHARED_POOL = None
_SHARED_POOL_LOCK = threading.Lock()
SHARED_POOL_THREAD_PREFIX = "paimon-decode"


def _reset_shared_pool_after_fork() -> None:
    # a forked child inherits the pool OBJECT but none of its worker
    # threads — submitting to it would block forever. Drop it (and the lock,
    # which another thread may have held at fork time); the child lazily
    # builds its own.
    global _SHARED_POOL, _SHARED_POOL_LOCK
    _SHARED_POOL = None
    _SHARED_POOL_LOCK = threading.Lock()


import os as _os  # noqa: E402

if hasattr(_os, "register_at_fork"):
    _os.register_at_fork(after_in_child=_reset_shared_pool_after_fork)


def shared_executor():
    """The process-wide decode thread pool (lazily created, never torn down
    mid-run). Manifest and data-file decodes release the GIL in pyarrow/zstd,
    so threads give real parallelism — but constructing a ThreadPoolExecutor
    per call costs thread spawn/join on every small read. One shared pool
    amortizes that. Tasks submitted here must never themselves submit to this
    pool (deadlock under a full queue); both call sites (scan manifest reads,
    read-path file decodes) are leaf work. Fork-safe: see
    _reset_shared_pool_after_fork.

    Sizing: PAIMON_TPU_SHARED_POOL_WORKERS env overrides; default covers the
    common 8-way decode fan-out even on small hosts."""
    global _SHARED_POOL
    if _SHARED_POOL is None:
        with _SHARED_POOL_LOCK:
            if _SHARED_POOL is None:
                import os
                from concurrent.futures import ThreadPoolExecutor

                workers = int(os.environ.get("PAIMON_TPU_SHARED_POOL_WORKERS", "0"))
                if workers <= 0:
                    workers = min(16, max(8, (os.cpu_count() or 4) + 4))
                _SHARED_POOL = ThreadPoolExecutor(
                    max_workers=workers, thread_name_prefix=SHARED_POOL_THREAD_PREFIX
                )
    return _SHARED_POOL


def on_shared_pool() -> bool:
    """Whether the calling thread is a worker of the shared pool: work that
    would fan out over the pool runs in turn there instead (see
    shared_executor: a pool task never submits to its own pool)."""
    return threading.current_thread().name.startswith(SHARED_POOL_THREAD_PREFIX)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory. Where JAX_COMPILATION_CACHE_DIR is set, JAX has
    already taken the directory from the environment and nothing is set in
    code; otherwise the cache lives at `<checkout>/.jax_cache` (a fixed path
    inside the tree, listed in .gitignore — the path is part of the cache
    key, so a directory that moves never hits). Called once by every process
    entry that reaches a kernel: chip_smoke.py, bench.py, benchmarks/*,
    `python -m paimon_tpu`, cluster workers."""
    import os

    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        checkout = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        path = os.path.join(checkout, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    if not _cpu_asked_for():
        # the merge kernels are many small jits (one per lane arity and pad
        # bucket), each seconds to compile for an accelerator: JAX's default
        # 1 s threshold would leave most of them uncached. A process pinned
        # to the CPU (tests, cluster workers) keeps the default, so its
        # millisecond compiles do not pile up on disk
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def _cpu_asked_for() -> bool:
    """JAX_PLATFORMS=cpu: the caller pinned this process to the CPU (read
    from the environment, so asking never initialises a backend)."""
    import os

    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def require_device() -> tuple[str, str, int]:
    """(platform, device_kind, device count) of the live backend, for scripts
    that measure. A measurement must name the device it ran on and must not
    land on the CPU by accident: anything but a TPU raises, unless the
    caller asked for the CPU itself with JAX_PLATFORMS=cpu — then the rows
    say `cpu`."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not _cpu_asked_for():
        raise RuntimeError(
            f"no TPU: the live JAX backend is {platform!r} "
            "(set JAX_PLATFORMS=cpu to measure the CPU on purpose)"
        )
    return platform, devices[0].device_kind, len(devices)


def new_file_name(prefix: str, ext: str | None = None) -> str:
    n = f"{prefix}-{uuid.uuid4().hex}"
    return f"{n}.{ext}" if ext else n


def partition_path(
    partition_keys: Sequence[str],
    partition: Sequence[Any],
    default_name: str = "__DEFAULT_PARTITION__",
) -> str:
    """Hive-style partition directory: k1=v1/k2=v2 ('' for unpartitioned).
    Null/empty values take partition.default-name (reference
    PartitionPathUtils.generatePartitionPath)."""
    if not partition_keys:
        return ""
    return "/".join(
        f"{k}={default_name if v is None or v == '' else v}"
        for k, v in zip(partition_keys, partition)
    )


def now_millis() -> int:
    return int(time.time() * 1000)


def dumps(obj: Any) -> str:
    return json.dumps(obj, separators=(",", ":"), default=_default)


def loads(s: str | bytes) -> Any:
    return json.loads(s)


def _default(o):
    import numpy as np

    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, (np.bool_,)):
        return bool(o)
    raise TypeError(f"not JSON serializable: {type(o)}")
