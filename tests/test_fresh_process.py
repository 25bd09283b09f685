"""What only a fresh interpreter shows: pytest's own process has long since
imported pyarrow and configured jax, so these run a child `python -c`.

1. A process whose FIRST pyarrow import would happen inside a paimon-flush
   pool thread must still write, commit and read back (pyarrow 25 segfaults
   once the thread that first imported it has exited — data/batch.py imports
   it with the package instead).
2. enable_compile_cache() takes the cache directory from
   JAX_COMPILATION_CACHE_DIR when that is set and otherwise resolves
   `<checkout>/.jax_cache`.
"""

import json
import os
import subprocess
import sys
import textwrap

from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.types import BIGINT, DOUBLE, STRING, RowType

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_py(code: str, extra_env: dict | None = None) -> str:
    env = {"PATH": "/usr/bin:/bin", "HOME": "/root", "JAX_PLATFORMS": "cpu", **(extra_env or {})}
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        timeout=180,
        cwd=REPO_ROOT,
        env=env,
    )
    assert r.returncode == 0, f"child exited {r.returncode}\n{r.stdout}\n{r.stderr}"
    return r.stdout


def test_fresh_interpreter_writes_commits_and_reads_back(tmp_warehouse):
    cat = FileSystemCatalog(tmp_warehouse, commit_user="parent")
    cat.create_table(
        "db.fresh",
        RowType.of(("k", BIGINT(False)), ("v", DOUBLE()), ("s", STRING())),
        primary_keys=["k"],
        options={"bucket": "1"},
    )
    for attempt in range(5):
        # two writers in a row: the first flush thread imports pyarrow and
        # exits with its writer, the second one builds arrow arrays
        out = _run_py(
            f"""
            import json, sys
            assert "pyarrow" not in sys.modules
            from paimon_tpu.table import load_table
            t = load_table("{tmp_warehouse}/db.db/fresh", commit_user="child{attempt}")
            for k, v, s in [({attempt}, {attempt}.5, "a{attempt}"), (100, 1.0, None)]:
                wb = t.new_batch_write_builder(); w = wb.new_write()
                w.write({{"k": [k], "v": [v], "s": [s]}})
                wb.new_commit().commit(w.prepare_commit())
                del w, wb
            rb = t.new_read_builder()
            print(json.dumps(rb.new_read().read_all(rb.new_scan().plan()).to_pylist()))
            """
        )
        rows = json.loads(out.strip().splitlines()[-1])
        assert rows == [[i, i + 0.5, f"a{i}"] for i in range(attempt + 1)] + [[100, 1.0, None]]


def test_compile_cache_honours_env_and_defaults_into_checkout(tmp_path):
    code = """
        import jax
        from paimon_tpu.utils import enable_compile_cache
        print(enable_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
        print(jax.config.jax_persistent_cache_min_compile_time_secs)
    """
    given = str(tmp_path / "xla-cache")
    # pinned to the CPU: JAX's own threshold stays, millisecond jits are not persisted
    assert _run_py(code, {"JAX_COMPILATION_CACHE_DIR": given}).split() == [given, given, "1.0"]
    default = os.path.join(REPO_ROOT, ".jax_cache")
    assert _run_py(code).split() == [default, default, "1.0"]
    # an accelerator may answer: every kernel is worth keeping (no backend is touched here)
    assert _run_py(code, {"JAX_PLATFORMS": "tpu,cpu"}).split() == [default, default, "0.0"]
