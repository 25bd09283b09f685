"""The write path's spans, reduced per client operation.

`program_spans.py` reduces per `read_all`, which an ingest never opens. Here
an operation is one `pb:op` interval of the window (run.py's span around each
call of the op): a reduction sums the `pt:` spans of one name that BEGIN
inside the interval, on any thread (the flush worker's and the pools' spans
belong to the commit that waited for them), and takes the median over the
operations. It reads 0 where the window's operations opened no such span
(a program without the write path's spans), and None where the trace holds
no `pb:op` at all. The readers under `layer_metrics/` that time a layer of
the write path share this file; docs/tracing.md has the span names.
"""

from __future__ import annotations

import functools
import statistics

from program_spans import PREFIX, WINDOW, Span, nest, overlap

OPERATION = "pb:op"
# spans that only hold other spans: client time in them and in no child is
# time the program has not named
CONTAINERS = ("prepare_commit", "flush", "compact")


class IngestSpans:
    def __init__(self, spans: list[Span], ops: list[tuple], window: tuple[float, float] | None):
        nest(spans)
        self.spans = spans
        if window is not None:
            ops = [o for o in ops if window[0] <= o[0] < window[1]]
        self.ops = sorted(ops)  # (start, end, line)

    def per_op_ms(self, name: str, less: tuple = ()) -> list[float]:
        """Per operation: the length of the spans called `name` that begin
        inside it, all threads, less the spans called any of `less` that lie
        inside them on their own thread."""
        def inside(span: Span) -> float:
            return sum((c.end - c.start) if c.name in less else inside(c) for c in span.children)

        mine = [s for s in self.spans if s.name == name]
        out = []
        for start, end, _ in self.ops:
            out.append(sum((s.end - s.start) - (inside(s) if less else 0.0)
                           for s in mine if start <= s.start < end) * 1e3)
        return out

    def unattributed_share(self) -> float | None:
        """Part of the operations' time, on the thread that runs them, under
        no `pt:` span that names its work (any but the containers)."""
        total = sum(end - start for start, end, _ in self.ops)
        if total <= 0:
            return None
        lines = {line for _, _, line in self.ops}
        leaves: list[list[float]] = []
        for s, e in sorted((s.start, s.end) for s in self.spans if s.line in lines and s.name not in CONTAINERS):
            if leaves and s <= leaves[-1][1]:
                leaves[-1][1] = max(leaves[-1][1], e)
            else:
                leaves.append([s, e])
        named = overlap([(s, e) for s, e, _ in self.ops], [(s, e) for s, e in leaves])
        return 1.0 - named / total


@functools.lru_cache(maxsize=2)
def load(path: str) -> IngestSpans:
    """One parse a trace file, shared by the readers."""
    from jax.profiler import ProfileData

    spans, ops, window = [], [], None
    for pi, plane in enumerate(ProfileData.from_file(path).planes):
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                start, end = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
                if e.name.startswith(PREFIX):
                    spans.append(Span(e.name[len(PREFIX):], start, end, (pi, li), dict(e.stats)))
                elif e.name == OPERATION:
                    ops.append((start, end, (pi, li)))
                elif e.name == WINDOW and window is None:
                    window = (start, end)
    return IngestSpans(spans, ops, window)


# ---- what the readers under layer_metrics/ call -----------------------------

def median_ms(w, name: str, less: tuple = ()) -> float | None:
    per_op = load(w.trace.path).per_op_ms(name, less)
    return statistics.median(per_op) if per_op else None


def unattributed_share(w) -> float | None:
    return load(w.trace.path).unattributed_share()
