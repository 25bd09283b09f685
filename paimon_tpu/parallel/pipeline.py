r"""Pipelined split scheduler: overlap IO, decode, and device merge across
buckets.

The reference's only cross-file parallelism is running many Flink/Spark tasks
at once (one split per task, MergeTreeSplitGenerator.java:38); inside one
process our hot paths used to drive splits, compaction sections, and flush
encodes strictly serially, so the device merge kernel idled while parquet
bytes were fetched and decoded — and vice versa. This module supplies the
staged execution the decode subsystem and caches were missing: a
bounded-readahead, ordered, multi-stage scheduler in the MonetDB/X100
pipelined-vectorized tradition, the cross-file analog of the double-buffered
tile transfer already used inside ops/merge (deduplicate_tiled_dispatch).

Stage map (who overlaps with whom):

    fetch bytes -> decode to KVBatch -> device merge -> emit
    \_________________  _____________/   \____  ____/     \_ consumer thread,
                      \/                      \/              strict input order
         pipeline worker threads        dispatched by the
         (split i+1, i+2, ...)          worker, so split i's
                                        kernel runs while
                                        split i+1 decodes

Three consumers ride the same primitive:

  * table/read.py — a multi-bucket scan prefetches and decodes split i+1
    (file bytes through RetryingFileIO, so PR 3's transient-retry
    classification applies inside the worker) while split i merges on
    device; batches emit in deterministic split order regardless of
    completion order.
  * core/compact.py — a rewrite's sections overlap file reads, merge
    dispatch, and output encode instead of reading every input before the
    first merge.
  * core/writer.py — the parquet/native encode of a rolled file runs on a
    flush worker while the next memtable fills; prepare_commit is the
    barrier.

Configuration: `scan.prefetch-splits` (readahead depth, default 2; 0 disables
pipelining everywhere and restores the strictly sequential path) and
`scan.parallelism` (stage worker threads; also bounds the per-file decode
fan-out of bounded_map).

Determinism contract: map_ordered emits results in submission order, and each
item's work function is self-contained, so pipelined output is BIT-IDENTICAL
to the sequential path (the randomized oracle pins this). Exceptions from any
worker propagate to the consumer at that item's position; the pool always
shuts down (no leaked threads) whether the generator is exhausted, closed
early, or unwound by an error.

Pool discipline: pipeline stages run on their OWN short-lived executor, never
on the process-wide shared decode pool — stage work itself fans out per-file
decodes to that shared pool (utils.shared_executor), and submitting to a pool
from one of its own workers deadlocks once the queue fills. bounded_map is
the leaf-level helper that does use the shared pool, with a sliding window so
`scan.parallelism` bounds in-flight decodes without a pool per call.
"""

from __future__ import annotations

from collections import deque
from typing import Callable, Iterable, Iterator, Sequence, TypeVar

__all__ = ["SplitPipeline", "bounded_map", "pipeline_config"]

T = TypeVar("T")
R = TypeVar("R")

# thread-name prefixes (the conftest leak assertion keys off these: pipeline
# pools are per-run and must be gone after every test; the shared decode pool
# is process-wide by design and exempt)
PIPELINE_THREAD_PREFIX = "paimon-pipeline"
FLUSH_THREAD_PREFIX = "paimon-flush"


def pipeline_config(options) -> tuple[int, int | None]:
    """(depth, parallelism) from a CoreOptions — the one seam every consumer
    reads, so `scan.prefetch-splits = 0` disables pipelining everywhere."""
    from ..options import CoreOptions

    depth = options.options.get(CoreOptions.SCAN_PREFETCH_SPLITS)
    par = options.options.get(CoreOptions.SCAN_PARALLELISM)
    return (max(int(depth or 0), 0), None if par is None else max(int(par), 1))


def _warm_decode_state() -> None:
    """pyarrow's lazily-initialized process globals segfault when first-ever
    init races across two threads (see core.read._ensure_arrow_decode_
    initialized) — warm them on the submitting thread before any worker
    decodes."""
    from ..core.read import _ensure_arrow_decode_initialized

    _ensure_arrow_decode_initialized()


class SplitPipeline:
    """Bounded-readahead ordered executor over per-item work functions.

    depth D keeps at most D+1 items in flight (the one the consumer waits on
    plus D prefetched), bounding the memory high-water at D+1 decoded splits.
    parallelism caps concurrent workers (default min(depth+1, 4) — readahead
    deeper than the worker count just queues).
    """

    def __init__(
        self,
        parallelism: int | None = None,
        depth: int = 2,
        stage: str = "scan",
    ):
        self.depth = max(int(depth), 0)
        self.parallelism = parallelism
        self.stage = stage

    def _workers(self) -> int:
        if self.parallelism is not None and self.parallelism > 0:
            return self.parallelism
        return max(1, min(self.depth + 1, 4))

    def map_ordered(self, items: Iterable[T], fn: Callable[[T], R]) -> Iterator[R]:
        """Yield fn(item) for every item, in input order, computing up to
        `depth` items ahead of the consumer. Exceptions raised by fn surface
        at that item's position; on error or early close every in-flight
        task is cancelled/awaited and the pool is torn down."""
        items = list(items)
        if self.depth == 0 or len(items) <= 1:
            for x in items:
                yield fn(x)
            return
        from concurrent.futures import ThreadPoolExecutor

        from ..metrics import carried, pipeline_metrics, span

        _warm_decode_state()
        g = pipeline_metrics()
        prefetched = g.counter("splits_prefetched")
        busy = g.histogram(f"{self.stage}_busy_ms")
        wait = g.histogram(f"{self.stage}_wait_ms")
        high_water = g.gauge("queue_depth_high_water")

        @carried  # the worker's spans name the operation and span that submitted the item
        def timed_fn(x: T) -> R:
            with span(f"pipeline.{self.stage}", histogram=busy):
                return fn(x)

        window = self.depth + 1
        ex = ThreadPoolExecutor(
            max_workers=min(self._workers(), window),
            thread_name_prefix=f"{PIPELINE_THREAD_PREFIX}-{self.stage}",
        )
        inflight: deque = deque()
        try:
            it = iter(items)
            for x in it:
                inflight.append(ex.submit(timed_fn, x))
                if len(inflight) > 1:
                    prefetched.inc()
                if len(inflight) > high_water.value:
                    high_water.set(len(inflight))
                if len(inflight) >= window:
                    break
            while inflight:
                with span(f"pipeline.{self.stage}.wait", histogram=wait):
                    result = inflight.popleft().result()  # re-raises worker errors
                for x in it:  # top the window back up before yielding
                    inflight.append(ex.submit(timed_fn, x))
                    prefetched.inc()
                    if len(inflight) > high_water.value:
                        high_water.set(len(inflight))
                    break
                yield result
        finally:
            for f in inflight:
                f.cancel()
            # wait=True: a worker mid-decode finishes (its result is dropped),
            # so no thread outlives the generator — the conftest leak
            # assertion pins this
            ex.shutdown(wait=True, cancel_futures=True)


def bounded_map(
    fn: Callable[[T], R], items: Sequence[T], parallelism: int | None = None
) -> list[R]:
    """Ordered map over the process-wide shared decode pool with at most
    `parallelism` items in flight (None = pool width, 1 = strictly serial).

    This is the leaf-level decode fan-out (per-file reads, manifest decodes):
    tasks submitted here must never themselves submit to the shared pool.
    A sliding window instead of executor.map lets `scan.parallelism` bound
    concurrency without constructing a pool per call."""
    items = list(items)
    if len(items) <= 1 or (parallelism is not None and parallelism <= 1):
        return [fn(x) for x in items]
    _warm_decode_state()
    from ..metrics import carried
    from ..utils import shared_executor

    fn = carried(fn)  # a file decoded on a pool thread names the operation and span that asked
    ex = shared_executor()
    if parallelism is None or parallelism >= len(items):
        return list(ex.map(fn, items))
    results: list[R] = []
    window: deque = deque()
    try:
        for x in items:
            window.append(ex.submit(fn, x))
            if len(window) >= parallelism:
                results.append(window.popleft().result())
        while window:
            results.append(window.popleft().result())
    finally:
        for f in window:
            f.cancel()
    return results
