"""Test config: force an 8-device virtual CPU mesh before jax initializes,
so multi-chip sharding paths are exercised without TPU hardware (the driver
separately dry-runs the real multi-chip path via __graft_entry__)."""

import os

# PAIMON_TEST_PLATFORM=tpu runs the kernel suites on the real chip
_platform = os.environ.get("PAIMON_TEST_PLATFORM", "cpu")
# exercise the device dispatch policy (compact/delta link encodings) even on
# the CPU backend, where production dispatch skips them (no link to save)
os.environ.setdefault("PAIMON_TPU_FORCE_COMPACT", "1")
# likewise pin the device merge kernels: production adapts to the host
# lexsort engine on a CPU-only backend (mergefn.effective_sort_engine), but
# the suite's job is to exercise the device dispatch path on the virtual mesh
os.environ.setdefault("PAIMON_TPU_FORCE_DEVICE_ENGINE", "1")
os.environ["JAX_PLATFORMS"] = _platform
flags = os.environ.get("XLA_FLAGS", "")
if _platform == "cpu" and "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

if _platform != "cpu":
    from paimon_tpu.utils import enable_compile_cache

    enable_compile_cache()

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _fresh_fragment_cache():
    """Tests are independent: the distributed-SQL fragment-result cache is
    process-global (keyed on table path + snapshot), so a test repeating an
    aggregate another test already ran would silently skip the scatter it
    means to exercise. Clear it around every test."""
    from paimon_tpu.sql.cluster import clear_fragment_cache

    clear_fragment_cache()
    yield
    clear_fragment_cache()


@pytest.fixture(autouse=True)
def _no_worker_thread_leaks():
    """Fail any test that leaves the pipelined scheduler's non-daemon worker
    threads alive (paimon-pipeline-* stage pools, paimon-flush writer
    offload, the paimon-compactor adaptive-compaction scheduler). The
    process-wide shared decode pool (paimon-decode) is exempt: it is never
    torn down by design. Abandoned executors tear down via
    ThreadPoolExecutor's weakref callback, so collect + briefly wait before
    declaring a leak."""
    yield
    import gc
    import threading
    import time

    def leaked():
        return [
            t
            for t in threading.enumerate()
            if t.is_alive()
            and not t.daemon
            and t.name.startswith(
                ("paimon-pipeline", "paimon-flush", "paimon-compactor", "paimon-subtail", "paimon-subhb", "paimon-qryref", "paimon-gw", "mega-")
            )
        ]

    if leaked():
        gc.collect()
        deadline = time.time() + 3.0
        while leaked() and time.time() < deadline:
            time.sleep(0.05)
    assert not leaked(), f"leaked non-daemon worker threads: {[t.name for t in leaked()]}"


@pytest.fixture(autouse=True)
def _no_child_process_leaks():
    """Fail any test that leaves a live child OS process behind. The
    process-grain soak (tests/test_proc_soak.py) spawns writer/reader
    subprocesses; a supervisor bug that orphans one would keep mutating the
    warehouse under every later test. Zombies (already-exited, not yet
    reaped) are ignored; live children get a short grace to finish exiting."""
    yield
    import time

    def live_children():
        pid = os.getpid()
        kids = []
        try:
            for task in os.listdir(f"/proc/{pid}/task"):
                try:
                    with open(f"/proc/{pid}/task/{task}/children") as f:
                        kids += [int(p) for p in f.read().split()]
                except OSError:
                    pass
        except OSError:
            return []  # no /proc: nothing to check on this platform
        alive = []
        for k in kids:
            try:
                with open(f"/proc/{k}/stat") as f:
                    stat = f.read()
                if stat.rsplit(")", 1)[1].split()[0] != "Z":
                    alive.append(k)
            except OSError:
                pass  # exited between listing and stat
        return alive

    leaked = live_children()
    if leaked:
        deadline = time.time() + 5.0
        while leaked and time.time() < deadline:
            time.sleep(0.1)
            leaked = live_children()
    assert not leaked, f"child processes outlived the test: {leaked}"


@pytest.fixture(scope="session", autouse=True)
def _forced_encoder_coverage():
    """When a verify stage forces PAIMON_TPU_PARQUET_ENCODER=native, the run
    must actually have routed parquet writes through the native encoder —
    a stage that silently fell back everywhere would prove nothing. Uses the
    encode subsystem's process-lifetime counter (registry.reset()-proof)."""
    yield
    if os.environ.get("PAIMON_TPU_PARQUET_ENCODER") == "native":
        from paimon_tpu.encode import files_native_total

        assert files_native_total() > 0, (
            "PAIMON_TPU_PARQUET_ENCODER=native was forced but no file was "
            "natively encoded in this session"
        )


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def tmp_warehouse(tmp_path):
    w = tmp_path / "warehouse"
    w.mkdir()
    return str(w)
