"""The pieces of the harness that need no run: the manifest check, the union
of intervals behind busy time, the percentile, the names of device ops."""

import json
import os
import shutil

import check_manifest
import run
import trace_reader

BENCH, ROOT = run.HERE, run.ROOT


def test_manifest_of_this_repo_has_no_fault():
    assert check_manifest.check() == []


def test_manifest_check_finds_faults(tmp_path):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copytree(os.path.join(BENCH, "configs"), tmp_path / "perfbench" / "configs")
    bench["per_layer"].append({"name": "no_reader", "unit": "rows per s", "better": "up", "source": "program_span",
                               "layer": "x", "moves": "no_such_metric"})
    bench["workloads"][0]["why"] = "x" * 201
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    faults = "\n".join(check_manifest.check(str(tmp_path)))
    for expected in ("no layer_metrics/no_reader.py", "unit 'rows per s'", "better 'up'", "201 characters",
                     "moves no_such_metric, which is no end-to-end metric"):
        assert expected in faults


def test_union_of_intervals():
    assert trace_reader.union_seconds([]) == 0
    assert trace_reader.union_seconds([(0, 2), (1, 3), (5, 6), (5.5, 5.75)]) == 4
    assert trace_reader.union_seconds([(3, 4), (0, 1)]) == 2


def test_p90_reader_is_nearest_rank_and_silent_on_few_operations():
    import types

    reader = run.load_module("layer_metrics", "op_ms_p90")
    window = lambda ops: types.SimpleNamespace(span_s=lambda name: ops)  # noqa: E731
    assert reader.read(window([i / 1e3 for i in range(100, 0, -1)])) == 90
    assert reader.read(window([i / 1e3 for i in range(1, 21)])) == 18
    assert reader.read(window([0.1] * 19)) is None


def test_device_op_names_are_short():
    text = ("%sort.12 = (u32[1048576]{0:T(1024)}, s32[1048576]{0:T(1024)S(1)}) "
            "sort(u32[1048576]{0:T(1024)S(1)} %a, s32[1048576]{0:T(1024)S(1)} %iota.1), dimensions={0}")
    assert trace_reader.short_op_name(text, "jit_f(5843045858094918714)") == "jit_f/sort.12:u32[1048576],s32[1048576]"
    assert trace_reader.short_op_name("not an instruction", None) == "not an instruction"


def test_a_trace_without_a_device_plane_is_an_error_not_host_threads(tmp_path):
    import jax
    import jax.numpy as jnp
    import pytest

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation(trace_reader.SPAN_PREFIX + trace_reader.WINDOW_SPAN):
        jnp.sort(jnp.arange(4096)[::-1]).block_until_ready()
    jax.profiler.stop_trace()
    path = trace_reader.newest_xplane(str(tmp_path))
    with pytest.raises(ValueError, match="no /device:TPU"):
        trace_reader.Trace(path)
    assert trace_reader.Trace(path, host_stand_in=True).window_s > 0
