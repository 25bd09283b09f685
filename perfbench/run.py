#!/usr/bin/env python3
"""perfbench/run.py: one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <config>.<traffic> --seed <n> --seconds <s> --trace <0|1>

A cell is resolved by name, from data: `configs/<config>.json` (the file the
cell's configuration names), `traffic/<traffic>.json` (which names its op),
`ops/<op>.py`, every `end_to_end/<name>.py` and, for a traced run, every
`layer_metrics/<name>.py` that BENCHMARK.json gives the cell. Adding a
configuration, a traffic mix, an op or a metric is adding files and entries;
nothing here changes.

A run: set-up (import, the device check, the compile cache inside the
checkout, the table written from --seed, os.sync(), three to six untimed
warm-up operations), then a window of nothing but identical operations timed
over whole operations, then (clock stopped) the device's peak memory, the
comparison of the last operation's output (and every operation's row count)
with the plain reference, and one JSON object as the last line of standard
output. Without a TPU, or with fewer
chips than the cell asks for, it exits 1 and prints no result.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()  # process start, to the few ms the interpreter took to get here

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

_FORBIDDEN_ENV = ("PAIMON_TPU_FORCE_", "PAIMON_TPU_SORT_ENGINE", "PAIMON_TPU_MERGE_ENGINE",
                  "PAIMON_TPU_JOIN_ENGINE", "PAIMON_TPU_DICT_ENGINE", "PAIMON_TPU_ENCODE_ENGINE",
                  "PAIMON_TPU_DECODE_ENGINE")


def log(obj) -> None:
    print("[perfbench] " + json.dumps(obj), flush=True)


def load_module(kind: str, name: str):
    """perfbench/<kind>/<name>.py, loaded by its file."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"perfbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(workload: str, root: str = ROOT) -> dict:
    """The cell's entry, its configuration, traffic and metric lists."""
    bench = load_json(root, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"perfbench: no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def mine(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "cell": cell,
        "config": load_json(root, entry["file"]),
        "traffic": load_json(HERE, "traffic", cell["traffic"] + ".json"),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


class Spans:
    """The benchmark's own spans, around its calls into the program: kept in
    memory as (name, start, end) on perf_counter; in a traced run each is
    also a TraceAnnotation `pb:<name>`, so the trace carries them on its own
    clock beside the device's operations."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []
        self.annotation = None

    @contextlib.contextmanager
    def span(self, name: str):
        note = self.annotation("pb:" + name) if self.annotation else contextlib.nullcontext()
        with note:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def durations(self, name: str, since: float) -> list[float]:
        return [e - s for n, s, e in self.records if n == name and s >= since]


class CompileMeter:
    """The XLA programs this process asked for, compiled or loaded from the
    persistent cache, and the seconds that took (jax.monitoring; copied from
    chip_smoke.py's DeviceMeter)."""

    def __init__(self):
        from jax import monitoring

        self.requests = 0
        self.hits = 0
        self.compile_s = 0.0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.compile_s += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> dict:
        return {"requests": self.requests, "cache_hits": self.hits, "compile_s": self.compile_s}


def check_device(chips: int):
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"perfbench: no TPU: jax.devices()[0].platform == {platform!r}; nothing was run")
    if len(devices) < chips:
        raise SystemExit(f"perfbench: the cell asks for {chips} chips and JAX finds {len(devices)}; nothing was run")
    return devices


def peak_device_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *, need_chip: bool = True,
             root: str = ROOT) -> dict:
    """One run; returns the result object that main() prints last."""
    spec = resolve(workload, root)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]

    import jax

    devices = check_device(cell["chips"]) if need_chip else jax.devices()
    import paimon_tpu  # noqa: F401  (turns on x64 before any array exists)
    from paimon_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    spans = Spans()
    workdir = tempfile.mkdtemp(prefix="perfbench_")
    try:
        # ---- set-up: the table from the seed, then the warm-up operations
        op = load_module("ops", traffic["op"]).Op(config, seed, workdir, spans)
        os.sync()  # write-back of the table's files must not overlap the window
        t_loaded = time.perf_counter()
        warm, steady = _warm_up(op, meter, traffic)
        log({"seed": seed, "workload": workload, "seconds": seconds, "trace": int(trace), "compile_cache": cache_dir,
             "load_s": t_loaded - _T_START, "warmup_op_s": warm, "warmup_steady": steady,
             "programs_in_setup": meter.snapshot(), "counters_after_warmup": _cache_gauges(op.counters()),
             **op.describe()})
        trace_dir = os.path.join(workdir, "trace")
        counters_before, programs_before = op.counters(), meter.snapshot()
        if trace:
            options = jax.profiler.ProfileOptions()
            options.host_tracer_level = 1  # TraceAnnotations only: a long host-bound window stays small
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            spans.annotation = jax.profiler.TraceAnnotation
        gc.collect()
        gc.freeze()

        # ---- the window: nothing but operations, timed over whole operations
        # The client releases each result before it asks again, so every
        # operation starts from the same state of the process. (What the
        # client holds changes what glibc's allocator gives back to the system
        # between operations, and with it the operation's time, PERF.md
        # section 6.) So only the last output is compared in full; of every
        # output the row count is noted, which costs nothing.
        op_s, rows_out, last = [], [], None
        with spans.span("window"):
            t0 = time.perf_counter()
            t_op = t0
            while True:
                last = None
                with spans.span("op"):
                    last = op()
                t_end = time.perf_counter()
                op_s.append(t_end - t_op)
                rows_out.append(op.rows_of(last))
                if t_end - t0 >= seconds:
                    break
                t_op = t_end
        elapsed = t_end - t0
        setup_s = t0 - _T_START

        # ---- the clock has stopped
        gc.unfreeze()
        if trace:
            jax.profiler.stop_trace()
            spans.annotation = None
        counters_after, programs_after = op.counters(), meter.snapshot()
        memory_peak = peak_device_bytes(devices)
        log({"op_s": op_s})
        rows = len(op_s) * op.rows_per_op

        numbers = _compare(op, last, rows_out)
        del last
        correct = all(value <= limit for _, value, limit in numbers)

        device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": memory_peak}
        result = {"correct": correct, "attempted": len(op_s), "failed": 0}
        window = types.SimpleNamespace(
            op_s=op_s, elapsed_s=elapsed, setup_s=setup_s, rows=rows, rows_per_op=op.rows_per_op, chips=cell["chips"],
            span_s=lambda name: spans.durations(name, t0), counters_before=counters_before,
            counters_after=counters_after, programs_before=programs_before, programs_after=programs_after,
            device_kind=devices[0].device_kind, peaks=load_json(HERE, "peaks.json"), memory_peak_bytes=memory_peak)
        if trace:
            import trace_reader

            window.trace = trace_reader.Trace(trace_reader.newest_xplane(trace_dir),
                                              host_stand_in=not need_chip)  # tests only
            window.busy_s = window.trace.busy_s()
        # one reader a metric, found by its name: end_to_end/<name>.py, layer_metrics/<name>.py
        kind, wanted = ("layer_metrics", spec["per_layer"]) if trace else ("end_to_end", spec["end_to_end"])
        metrics = {}
        for m in wanted:
            value = load_module(kind, m["name"]).read(window)
            if value is not None:  # a reader with nothing to read leaves its metric out
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        if trace:
            device["busy_s"] = sum(window.busy_s.values()) / max(1, cell["chips"])
            device["window_s"] = window.trace.window_s
            result["breakdown"] = {"device_ops": window.trace.device_ops(), "idle_gaps": window.trace.idle_gaps()}
        result["device"] = device
        result["window"] = {"elapsed_s": elapsed, "operations": len(op_s), "rows": rows, "setup_s": setup_s,
                            "op_s_median": statistics.median(op_s)}
        result["compared"] = {name: {"value": value, "limit": limit} for name, value, limit in numbers}
        for name, value, limit in numbers:
            print(f"perfbench compared {name}: {value} limit {limit}", file=sys.stderr)
        print(f"perfbench correct: {correct} ({len(numbers)} numbers over the compared outputs)", file=sys.stderr, flush=True)
        return result
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _warm_up(op, meter, traffic) -> tuple[list[float], bool]:
    """Untimed operations until one requests no program and takes at most
    `warmup_slack` longer than the one before it: at least `warmup_ops_min`,
    at most `warmup_ops_max`. Returns their wall times and whether the last
    was steady."""
    warm, out = [], None
    while True:
        before = meter.requests
        out = None  # as in the window: a result is released before the next is asked for
        t0 = time.perf_counter()
        out = op()
        warm.append(time.perf_counter() - t0)
        steady = meter.requests == before and len(warm) >= 2 and warm[-1] <= warm[-2] * (1 + traffic["warmup_slack"])
        if (len(warm) >= traffic["warmup_ops_min"] and steady) or len(warm) >= traffic["warmup_ops_max"]:
            return warm, steady


def _cache_gauges(counters: dict) -> dict:
    return {k: v for k, v in counters.items() if k.startswith("cache")}


def _compare(op, last, rows_out: list[int]) -> list[tuple[str, int, int]]:
    """The last operation's output against the plain reference, column by
    column, and every operation's row count against the reference's."""
    import reference

    want = op.reference_columns()
    want_rows = len(next(iter(want.values()))[0])
    numbers = [("operations_with_wrong_row_count", sum(1 for n in rows_out if n != want_rows), 0)]
    numbers += reference.compare(op.output_columns(last), want)
    return numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bad = sorted(k for k in os.environ if k.startswith(_FORBIDDEN_ENV))
    if bad:
        print(f"perfbench: refusing to run with engine overrides in the environment: {bad}", file=sys.stderr)
        return 1
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
