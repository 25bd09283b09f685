"""The actions CLI (`python -m paimon_tpu <action>`), mirroring the
reference's flink-action surface (flink/action/, 47 actions + procedures)."""

import json
import os
import subprocess
import sys

import pytest

from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.types import BIGINT, DOUBLE, RowType

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCHEMA = RowType.of(("id", BIGINT()), ("v", DOUBLE()))


def run_cli(*argv):
    r = subprocess.run(
        [sys.executable, "-m", "paimon_tpu", *argv],
        capture_output=True, text=True, timeout=180, cwd=REPO_ROOT,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": "/root",
             "JAX_ENABLE_X64": "true"},
    )
    assert r.returncode == 0, r.stderr
    return r.stdout.strip()


@pytest.fixture
def wh(tmp_warehouse):
    cat = FileSystemCatalog(tmp_warehouse, commit_user="setup")
    t = cat.create_table("db.t", SCHEMA, primary_keys=["id"], options={"bucket": "1", "write-only": "true"})
    for r in range(3):
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        w.write({"id": list(range(10)), "v": [float(r * 10 + i) for i in range(10)]})
        wb.new_commit().commit(w.prepare_commit())
    return tmp_warehouse


def test_cli_compact_query_tags_rollback(wh):
    base = ["--warehouse", wh, "--table", "db.t"]
    out = json.loads(run_cli("compact", "--full", *base))
    assert out["compacted"] is True
    rows = [json.loads(line) for line in run_cli("query", *base, "--limit", "5").splitlines()]
    assert len(rows) == 5
    rows = [json.loads(line) for line in run_cli(
        "query", *base, "--filter", '{"field": "id", "op": "=", "value": 3}').splitlines()]
    assert rows == [[3, 23.0]]
    run_cli("create-tag", *base, "--tag", "v1")
    assert json.loads(run_cli("list-tags", *base)) == {"v1": 4}
    out = json.loads(run_cli("delete", *base, "--where", '{"field": "id", "op": ">=", "value": 5}'))
    assert out["rows_deleted"] == 5
    run_cli("rollback-to", *base, "--to", "v1")
    rows = [json.loads(line) for line in run_cli("query", *base, "--limit", "100").splitlines()]
    assert len(rows) == 10  # rollback restored the tagged snapshot


def test_cli_sync_table_and_expire(wh, tmp_path):
    base = ["--warehouse", wh, "--table", "db.t"]
    stream = tmp_path / "cdc.jsonl"
    msgs = [
        {"payload": {"op": "c", "before": None, "after": {"id": 100, "v": 1.5}}},
        {"payload": {"op": "d", "before": {"id": 0, "v": 0.0}, "after": None}},
    ]
    stream.write_text("\n".join(json.dumps(m) for m in msgs))
    out = json.loads(run_cli("sync-table", *base, "--format", "debezium-json", "--input", str(stream)))
    assert out["records_applied"] == 2
    rows = [json.loads(line) for line in run_cli("query", *base, "--limit", "100").splitlines()]
    ids = {r[0] for r in rows}
    assert 100 in ids and 0 not in ids
    out = json.loads(run_cli("expire-snapshots", *base))
    assert "expired" in out


def test_cli_migrate(tmp_warehouse, tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    src = tmp_path / "legacy"
    src.mkdir()
    pq.write_table(pa.table({"a": [1, 2], "s": ["x", "y"]}), src / "part-0.parquet")
    out = json.loads(run_cli(
        "migrate-table", "--warehouse", tmp_warehouse, "--table", "db.mig",
        "--source-dir", str(src), "--format", "parquet",
    ))
    assert out["snapshot"] == 1
    rows = [json.loads(line) for line in run_cli(
        "query", "--warehouse", tmp_warehouse, "--table", "db.mig", "--limit", "10").splitlines()]
    assert rows == [[1, "x"], [2, "y"]]


def test_cli_sql_action(wh):
    rows = [json.loads(line) for line in run_cli(
        "sql", "--warehouse", wh, "SELECT id, v FROM db.t WHERE id >= 8 ORDER BY id").splitlines()]
    assert [r[0] for r in rows] == [8, 9]
    agg = [json.loads(line) for line in run_cli(
        "sql", "--warehouse", wh, "SELECT count(*), max(id) FROM db.t").splitlines()]
    assert agg == [[10, 9]]
    out = json.loads(run_cli("sql", "--warehouse", wh, "CALL sys.create_tag('db.t', 'via-sql')"))
    assert out["tag"] == "via-sql"
