#!/usr/bin/env python3
"""The program's own spans, read from a jax.profiler trace (.xplane.pb).

paimon_tpu opens a TraceAnnotation `pt:<name>` at each boundary of its read
path (docs/tracing.md has the names), with the stats `op` (the id
TableRead.read_all allotted to the operation) and `parent` (the name of the
span that caused it). They lie on the host plane, on the clock of the device's
events, one line a thread. `trace_reader.Trace` keeps the benchmark's `pb:`
spans and the device's operations; this module keeps the `pt:` ones, and the
readers under `layer_metrics/` that time a layer of the program share it.

Times are per operation begun inside `pb:window` (every operation where the
trace has no such span), of the thread that ran `read_all`. A span's self time
is its length less what its children on the same line cover, so self times
add up to the operation. A trace of a program without these spans holds no
operation, and every reduction here returns None.

    python3 perfbench/program_spans.py <file.xplane.pb | trace directory>

prints each span's count, total and self time, and the ten longest idle gaps
of the busiest device, each with the innermost `pt:` span open when it began.
"""

from __future__ import annotations

import functools
import os
import statistics
import sys
from dataclasses import dataclass, field

PREFIX = "pt:"
OPERATION = "read_all"
# spans that only hold other spans: time in them and in no child is time the
# program has not named
CONTAINERS = ("read_all", "split")
WINDOW = "pb:window"
# the jitted programs of paimon_tpu/ops/merge.py, by the names _jit gives them
MERGE_PROGRAMS = ("jit_dedup_select", "jit_merge_plan", "jit_partial_update", "jit_fused_partial_update")


@dataclass
class Span:
    name: str
    start: float  # seconds on the trace's clock
    end: float
    line: tuple  # (plane, line) indices: one thread
    stats: dict
    self_s: float = 0.0
    children: list = field(default_factory=list, repr=False)

    @property
    def op(self) -> int:
        return int(self.stats.get("op", 0))


def matches(name: str, wanted) -> bool:
    """`wanted` names a span (`merge.dispatch`) or a family (`decode` is
    decode.keys, decode.values, decode.all and decode.file)."""
    return any(name == w or name.startswith(w + ".") for w in wanted)


def nest(spans: list[Span]) -> None:
    """Fill `children` and `self_s` from the intervals, line by line."""
    by_line: dict[tuple, list[Span]] = {}
    for s in spans:
        by_line.setdefault(s.line, []).append(s)
    for line in by_line.values():
        line.sort(key=lambda s: (s.start, -s.end))
        stack: list[Span] = []
        for s in line:
            s.self_s = s.end - s.start
            while stack and stack[-1].end <= s.start:
                stack.pop()
            if stack:
                stack[-1].children.append(s)
                stack[-1].self_s -= min(s.end, stack[-1].end) - s.start
            stack.append(s)


class ProgramSpans:
    def __init__(self, spans: list[Span], window: tuple[float, float] | None = None):
        nest(spans)
        self.spans = spans
        self.window = window
        operations = [s for s in spans if s.name == OPERATION]
        if window is not None:
            operations = [s for s in operations if window[0] <= s.start < window[1]]
        self.operations = sorted(operations, key=lambda s: s.start)
        self.reader_lines = {s.line for s in self.operations}
        self._by_op: dict[int, list[Span]] = {s.op: [] for s in self.operations}
        for s in spans:
            if s.op in self._by_op:
                self._by_op[s.op].append(s)

    # ---- per operation -------------------------------------------------
    def self_ms(self, wanted) -> list[float]:
        """Per operation: self time of the spans named, on the reading thread."""
        return [sum(s.self_s for s in self._by_op[o.op] if s.line == o.line and matches(s.name, wanted)) * 1e3
                for o in self.operations]

    def busy_ms(self, wanted) -> list[float]:
        """Per operation: length of the spans named, summed over all threads."""
        return [sum(s.end - s.start for s in self._by_op[o.op] if matches(s.name, wanted)) * 1e3
                for o in self.operations]

    def unattributed_share(self) -> float | None:
        """Part of the operations' time that lies in a container span and in
        no child of it: work inside read_all that no span names."""
        total = sum(o.end - o.start for o in self.operations)
        if total <= 0:
            return None
        bare = sum(s.self_s for o in self.operations for s in self._by_op[o.op]
                   if s.line == o.line and s.name in CONTAINERS)
        return bare / total

    # ---- against the device --------------------------------------------
    def leaf_intervals(self) -> list[tuple[float, float]]:
        """Where the reading thread is inside a span that names its work (any
        `pt:` span of that thread but the containers), merged and sorted."""
        out: list[list[float]] = []
        for s, e in sorted((s.start, s.end) for s in self.spans
                           if s.line in self.reader_lines and s.name not in CONTAINERS):
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return [(s, e) for s, e in out]

    def idle_attributed_share(self, busy: list[tuple[float, float]], window: tuple[float, float]) -> float | None:
        """Part of a device's idle time in the window (the window less its
        busy intervals, given sorted) that lies under a leaf span."""
        if not self.operations:
            return None
        gaps = idle_gaps(busy, window)
        idle = sum(e - s for s, e in gaps)
        if idle <= 0:
            return None
        return overlap(gaps, self.leaf_intervals()) / idle

    def innermost_at(self, t: float) -> str:
        """Name of the innermost span of the reading thread open at `t`."""
        best = None
        for s in self.spans:
            if s.line in self.reader_lines and s.start <= t < s.end and (best is None or s.start >= best.start):
                best = s
        return best.name if best else "outside-read_all"

    def table(self) -> list[tuple[str, int, float, float]]:
        """(name, count, total seconds, self seconds) over the operations'
        spans on every thread, longest self time first."""
        rows: dict[str, list] = {}
        for o in self.operations:
            for s in self._by_op[o.op]:
                r = rows.setdefault(s.name, [0, 0.0, 0.0])
                r[0] += 1
                r[1] += s.end - s.start
                r[2] += s.self_s
        return sorted(((n, c, t, own) for n, (c, t, own) in rows.items()), key=lambda r: -r[3])


def idle_gaps(busy, window) -> list[tuple[float, float]]:
    """The stretches of `window` that no interval of `busy` (sorted) covers."""
    gaps, at = [], window[0]
    for s, e in busy:
        if s > at:
            gaps.append((at, min(s, window[1])))
        at = max(at, e)
    if window[1] > at:
        gaps.append((at, window[1]))
    return [(s, e) for s, e in gaps if e > s]


def overlap(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@functools.lru_cache(maxsize=2)
def load(path: str) -> ProgramSpans:
    """The `pt:` spans of a trace file; one parse a file, shared by the readers."""
    from jax.profiler import ProfileData

    spans, window = [], None
    for pi, plane in enumerate(ProfileData.from_file(path).planes):
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append(Span(e.name[len(PREFIX):], e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                                      (pi, li), dict(e.stats)))
                elif e.name == WINDOW and window is None:
                    window = (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
    return ProgramSpans(spans, window)


# ---- what the readers under layer_metrics/ call -----------------------------

def median_self_ms(w, *wanted) -> float | None:
    """Median over the window's operations of the reading thread's self time
    in the spans named; 0 where the operations opened none of them; None
    where the trace holds no operation of the program's."""
    per_op = load(w.trace.path).self_ms(wanted)
    return statistics.median(per_op) if per_op else None


def median_busy_ms(w, *wanted) -> float | None:
    per_op = load(w.trace.path).busy_ms(wanted)
    return statistics.median(per_op) if per_op else None


def counter_delta(w, group: str, name: str):
    """A counter of the program's registry over the window; None where the
    program has no such group (it was never touched, or it does not exist)."""
    if group not in w.counters_after:
        return None
    return w.counters_after[group].get(name, 0) - w.counters_before.get(group, {}).get(name, 0)


def counter_ratio(w, group: str, name: str, base_group: str, base_name: str) -> float | None:
    """delta(name) / delta(base) over the window: 0 where the count is 0 (or
    its group was never touched) and the base is not; None without a base."""
    n, base = counter_delta(w, group, name), counter_delta(w, base_group, base_name)
    return (n or 0) / base if base else None


def sort_busy_s(w) -> float | None:
    """Device seconds of the window in the sort instructions of the merge
    programs (union per device, summed); None where no merge program ran
    under a name of its own."""
    from trace_reader import union_seconds

    found, total = False, 0.0
    for ops in w.trace.devices.values():
        mine = [(s, e, n.split("/", 1)[1]) for s, e, n in ops if "/" in n and n.startswith(MERGE_PROGRAMS)]
        found = found or bool(mine)
        total += union_seconds((s, e) for s, e, instruction in mine if instruction.startswith("sort"))
    return total if found else None


def busiest_device(w):
    """(sorted busy intervals, window) of the device that was busy longest."""
    if not w.busy_s:
        return None
    ops = w.trace.devices[max(w.busy_s, key=w.busy_s.get)]
    return [(s, e) for s, e, _ in ops], w.trace.window


def describe(path: str) -> None:
    import trace_reader

    spans = load(path)
    print(f"{len(spans.operations)} operations" + (" inside pb:window" if spans.window else " (no pb:window: all)"))
    print(f"{'span':<22}{'count':>8}{'total s':>12}{'self s':>12}")
    for name, count, total, own in spans.table():
        print(f"{name:<22}{count:>8}{total:>12.4f}{own:>12.4f}")
    share = spans.unattributed_share()
    print("read_unattributed_share:", share)
    try:
        trace = trace_reader.Trace(path)
    except ValueError as e:  # no device plane or no pb:window: the spans are all there is to show
        print("no device side:", e)
        return
    busy = trace.busy_s()
    ops = trace.devices[max(busy, key=busy.get)]
    intervals = [(s, e) for s, e, _ in ops]
    print("idle_attributed_share:", spans.idle_attributed_share(intervals, trace.window))
    gaps = sorted(idle_gaps(intervals, trace.window), key=lambda g: g[0] - g[1])[:10]
    for s, e in gaps:
        print(f"idle {e - s:9.4f} s  began in pt:{spans.innermost_at(s)}")


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_reader

    describe(sys.argv[1] if not os.path.isdir(sys.argv[1]) else trace_reader.newest_xplane(sys.argv[1]))
