"""From a jax.profiler trace (.xplane.pb) to what the per-layer readers and the
`breakdown` need: per device the intervals in which an operation ran, and the
benchmark's own host spans, all on the trace's clock and clipped to the
measured window. The one trace parser of the benchmark.

A device plane is `/device:TPU:<n>`; its line `XLA Ops` holds one event per
operation executed, named by its whole HLO instruction, and its line `XLA
Modules` one event per program run. An operation is named here
`<program>/<instruction>:<result shapes>`, layouts left out. The benchmark's spans are TraceAnnotations whose names
start with `pb:` (run.py writes them), found on the host plane. A trace with
no device plane is an error: the profiler captured nothing of the chip, and no
number is made from host threads in its place. Only with `host_stand_in=True`
(the CPU backend, in tests) the executor threads' events that carry an
`hlo_op` stand in, so that the reduction can be tested without a chip.

`python3 perfbench/trace_reader.py <file.xplane.pb>` prints the planes and lines of
a trace, for a look by hand before trusting the reduction.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys

SPAN_PREFIX = "pb:"
WINDOW_SPAN = "window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
_INSTRUCTION = re.compile(r"^%(\S+) = (.*?) [\w-]+\(")
_LAYOUT = re.compile(r"\{[^}]*\}")


def newest_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def union_seconds(intervals) -> float:
    """Length of the union of [start, end) intervals, in their unit."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def short_op_name(instruction: str, module: str | None) -> str:
    """`%sort.12 = (u32[8]{0:T(1024)}, s32[8]{0}) sort(...)` of program
    `jit_f(123)` -> `jit_f/sort.12:u32[8],s32[8]`."""
    m = _INSTRUCTION.match(instruction)
    name = instruction
    if m:
        shapes = _LAYOUT.sub("", m.group(2)).strip("()").replace(" ", "")
        name = f"{m.group(1)}:{shapes}"
    if module:
        name = f"{module.split('(')[0]}/{name}"
    return name[:120]


def _module_at(modules, start):
    """Name of the program whose run covers `start`; modules sorted by start."""
    i = bisect.bisect_right(modules, (start, float("inf"), "")) - 1
    return modules[i][2] if i >= 0 and modules[i][1] >= start else None


class Trace:
    """devices: {ordinal: [(start_s, end_s, name)]}; spans: {name: [(start_s,
    end_s)]}; window: (start_s, end_s) of the `pb:window` span. Seconds on the
    trace's clock; device events are clipped to the window."""

    def __init__(self, path: str, host_stand_in: bool = False):
        data = _load(path)
        self.path = path
        self.spans: dict[str, list[tuple[float, float]]] = {}
        raw: dict[int, list[tuple[float, float, str]]] = {}
        host_planes = []
        for plane in data.planes:
            m = _DEVICE_PLANE.match(plane.name)
            if m:
                lines = {line.name: line for line in plane.lines}
                modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                                 for e in (lines[_MODULES_LINE].events if _MODULES_LINE in lines else ()))
                ops = raw.setdefault(int(m.group(1)), [])
                for e in (lines[_OPS_LINE].events if _OPS_LINE in lines else ()):
                    ops.append((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                                short_op_name(e.name, _module_at(modules, e.start_ns))))
            elif plane.name.startswith("/host:"):
                host_planes.append(plane)
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            self.spans.setdefault(e.name[len(SPAN_PREFIX):], []).append(
                                (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9))
        if not raw and not host_stand_in:
            raise ValueError(f"{path}: no /device:TPU:<n> plane in the trace; planes: "
                             f"{[plane.name for plane in data.planes]}")
        if not raw:  # the CPU backend, in tests: the executor threads' operations stand in
            raw[0] = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                       short_op_name(e.name, _stat(e, "hlo_module")))
                      for plane in host_planes for line in plane.lines for e in line.events
                      if e.duration_ns > 0 and _stat(e, "hlo_op") is not None]
        if WINDOW_SPAN not in self.spans:
            raise ValueError(f"{path}: no {SPAN_PREFIX}{WINDOW_SPAN} span in the trace")
        self.window = self.spans[WINDOW_SPAN][0]
        w0, w1 = self.window
        self.devices = {
            d: sorted((max(s, w0), min(e, w1), n) for s, e, n in ops if e > w0 and s < w1)
            for d, ops in raw.items()
        }

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> dict[int, float]:
        """Per device: seconds of the window in which some operation ran."""
        return {d: union_seconds((s, e) for s, e, _ in ops) for d, ops in self.devices.items()}

    def device_ops(self, top: int = 10) -> list[list]:
        """[name, seconds] of the operations that took most device time in the
        window, summed over the devices."""
        total: dict[str, float] = {}
        for ops in self.devices.values():
            for s, e, n in ops:
                total[n] = total.get(n, 0.0) + (e - s)
        return [[n, t] for n, t in sorted(total.items(), key=lambda kv: -kv[1])[:top]]

    def idle_gaps(self, top: int = 10) -> list[list]:
        """[name, seconds] of the longest stretches of the window in which the
        busiest device ran nothing, each named by the innermost benchmark span
        that was open when it began (`between-ops` where none was)."""
        busy = self.busy_s()
        if not busy:
            return []
        ops = self.devices[max(busy, key=busy.get)]
        w0, w1 = self.window
        gaps, at = [], w0
        for s, e, _ in ops:
            if s > at:
                gaps.append((at, s))
            at = max(at, e)
        if w1 > at:
            gaps.append((at, w1))
        gaps.sort(key=lambda g: g[0] - g[1])
        inner = sorted(((s, e, n) for n, ss in self.spans.items() if n != WINDOW_SPAN for s, e in ss),
                       key=lambda x: x[1] - x[0])
        out = []
        for g0, g1 in gaps[:top]:
            name = next((n for s, e, n in inner if s <= g0 < e), "between-ops")
            out.append([name, g1 - g0])
        return out


def describe(path: str) -> None:
    data = _load(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:4]:
                print(f"      {e.name!r} start_ns={e.start_ns} duration_ns={e.duration_ns} stats={dict(e.stats)}")


if __name__ == "__main__":
    describe(sys.argv[1] if not os.path.isdir(sys.argv[1]) else newest_xplane(sys.argv[1]))
