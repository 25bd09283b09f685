"""The CDC-upsert deployment at a size a test can hold (perfbench's cell
`cdc-upsert.zipf-ingest`): a Zipf upsert stream through the stream writer with
universal compaction on, read back against `perfbench/reference_ingest.py`;
merges whose downloads ask for no XLA program once their shape was met; the
write path's spans and counters (docs/tracing.md).
"""

import json
import os
import sys

import jax
import numpy as np
import pytest

import paimon_tpu as pt
from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.metrics import registry
from paimon_tpu.ops import merge as M
from paimon_tpu.options import CoreOptions

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perfbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference  # noqa: E402  (perfbench/reference.py)
import reference_ingest  # noqa: E402
from test_tracing import _inside, _named, traced  # noqa: E402

CONFIG = json.load(open(os.path.join(BENCH, "configs", "cdc-upsert.json")))
TRAFFIC = json.load(open(os.path.join(BENCH, "traffic", "zipf-ingest.json")))
SCHEMA = CONFIG["schema"]
KEYS, BATCH, COMMITS = 4_000, 600, 30


def _row_type():
    fields = []
    for name, spec in SCHEMA:
        base, _, rest = spec.partition(" ")
        fields.append((name, getattr(pt, base)(rest != "NOT NULL")))
    return pt.RowType.of(*fields)


def _table(warehouse):
    catalog = FileSystemCatalog(str(warehouse), commit_user="cdc")
    table = catalog.create_table(CONFIG["table"], _row_type(), primary_keys=CONFIG["primary_keys"],
                                 options={k: str(v) for k, v in CONFIG["options"].items()})
    ids = reference_ingest.key_universe(KEYS)
    wb = table.new_batch_write_builder()
    w = wb.new_write()
    w.write(reference.columns(ids, np.zeros(KEYS, dtype=np.int64), SCHEMA))  # run 0: every key once
    w.compact(full=True)
    wb.new_commit().commit(w.prepare_commit())
    w.close()
    return table, ids


def _read_columns(table):
    rb = table.new_read_builder()
    out = rb.new_read().read_all(rb.new_scan().plan())
    return {n: (np.asarray(out.column(n).values), out.column(n).valid_mask()) for n, _ in SCHEMA}


def _failed(numbers):
    return [(name, value) for name, value, limit in numbers if value > limit]


class CompileCount:
    """XLA programs requested, as perfbench's CompileMeter counts them."""

    def __init__(self):
        self.requests = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1


@pytest.fixture(scope="module")
def compiles():
    return CompileCount()


# ---- the deployment, read back against the plain reference ---------------------

@pytest.mark.parametrize("seed", [5, 2**31 + 17])
def test_zipf_upsert_stream_reads_back_each_keys_last_writer(tmp_path, seed):
    table, ids = _table(tmp_path / "warehouse")
    stream = reference_ingest.ZipfStream(seed, KEYS, CONFIG["keys"]["exponent"], BATCH)
    builder = table.new_stream_write_builder()
    writer, committer = builder.new_write(), builder.new_commit()
    sent, highest, compacted = [], table.store.snapshot_manager.latest_snapshot().id, 0
    for i in range(1, COMMITS + 1):
        positions = stream.positions(i)
        writer.write(reference_ingest.batch_columns(ids, positions, i, SCHEMA))
        snapshot_ids = committer.commit_messages(i, writer.prepare_commit())
        # acknowledged, in order: at least one snapshot, every id above all seen before
        assert snapshot_ids and min(snapshot_ids) > highest
        highest, compacted = max(snapshot_ids), compacted + (len(snapshot_ids) == 2)
        sent.append((i, positions))
        if i % 10 == 0:
            want = {n: (v, None) for n, v in reference_ingest.table_after(ids, sent, SCHEMA).items()}
            assert _failed(reference.compare(_read_columns(table), want)) == []
    assert compacted >= 5  # universal compaction ran underneath: commits that landed APPEND and COMPACT
    assert committer.commit_messages(COMMITS, []) == []  # a replayed identifier lands nothing
    writer.close()
    want = {n: (v, None) for n, v in reference_ingest.table_after(ids, sent, SCHEMA).items()}
    got = _read_columns(table)
    assert _failed(reference.compare(got, want)) == []
    assert np.array_equal(got["id"][0], ids)  # row for row: each key once, ascending
    # and the controls are not this table
    for control in (reference_ingest.control_last_commit_lost, reference_ingest.control_first_writer):
        broken = {n: (v, None) for n, v in control(ids, sent, SCHEMA).items()}
        assert [name for name, _ in _failed(reference.compare(got, broken))] == ["wrong_cells"]


def test_zipf_stream_is_skewed_scattered_and_a_function_of_the_seed():
    stream = reference_ingest.ZipfStream(11, 50_000, 0.99, 20_000)
    a, again, other = stream.positions(3), stream.positions(3), stream.positions(4)
    assert np.array_equal(a, again) and not np.array_equal(a, other)
    assert np.array_equal(reference_ingest.ZipfStream(11, 50_000, 0.99, 20_000).positions(3), a)
    assert not np.array_equal(reference_ingest.ZipfStream(12, 50_000, 0.99, 20_000).positions(3), a)
    keys, counts = np.unique(a, return_counts=True)
    assert 0.3 < len(keys) / len(a) < 0.7  # about half of a batch's rows are distinct keys
    hottest = keys[np.argsort(-counts)[:10]]
    assert counts.max() > 0.03 * len(a)  # rank 1 holds 1 / H(n, 0.99) of the draws
    assert np.ptp(hottest) > 10_000  # hot keys are not neighbours
    with pytest.raises(ValueError):
        stream.positions(0)


def test_the_configuration_states_the_programs_defaults_and_sets_none_of_them():
    stated = {k: v for k, v in CONFIG["program_defaults"].items() if k != "about"}
    for option in (CoreOptions.NUM_SORTED_RUNS_COMPACTION_TRIGGER, CoreOptions.COMPACTION_SIZE_RATIO,
                   CoreOptions.COMPACTION_MAX_SIZE_AMP_PERCENT):
        assert stated.pop(option.key) == option.default
        assert option.key not in CONFIG["options"]
    assert stated == {}
    assert CONFIG["options"]["write-only"] == "false" and CONFIG["rows"] * CONFIG["writer_tasks"] == CONFIG["source_rows"]
    assert TRAFFIC["batch_rows"] == CONFIG["commit_interval_rows"] == 100_000
    assert TRAFFIC["warmup_ops_min"] == TRAFFIC["warmup_ops_max"] == 10 and TRAFFIC["clients"] == 1


# ---- a steady write window asks for no program ----------------------------------

def _runs_with_overlap(n_a: int, overlap: int):
    """Two key-sorted runs whose merge has n_a + 40_000 - overlap winners."""
    a = np.arange(n_a, dtype=np.uint32) * 2
    b = np.concatenate([a[:overlap], np.arange(40_000 - overlap, dtype=np.uint32) * 2 + 1_000_001])
    return np.concatenate([a, np.sort(b)])[:, None], [0, n_a, n_a + 40_000]


@pytest.mark.parametrize("path", ["index", "compact", "batched"])
def test_twenty_winner_counts_at_one_pad_bucket_ask_for_one_program(compiles, path):
    counts = set()
    for step in range(21):
        lanes, offsets = _runs_with_overlap(60_000, 500 * step + 7)
        if step == 1:
            after_first = compiles.requests
        if path == "index":  # the writers' route: a flush, a compaction round (merge_async)
            take = M.deduplicate_resolve(M.deduplicate_select_async(lanes, None, compress=False))
        elif path == "compact":  # a read's single tile, link encodings on (conftest)
            handle = M.deduplicate_tiled_dispatch(lanes, offsets, tile_rows=1 << 20, compress=False)
            assert handle[0][0][0] == "compact"
            take = M.deduplicate_resolve_tiled(handle)
        else:  # a read cut into key-range tiles, one vmapped call
            lanes3 = np.concatenate([lanes, lanes[:30_000] + 1])
            handle = M.deduplicate_tiled_dispatch(lanes3, offsets + [len(lanes3)], tile_rows=50_000, compress=False)
            assert handle[0] == "batched"
            take = M.deduplicate_resolve_tiled(handle)
            lanes = lanes3
        keys = lanes[take, 0]
        assert np.array_equal(keys, np.unique(lanes[:, 0]))  # each key once, in key order
        counts.add(len(take))
    assert len(counts) == 21
    assert compiles.requests == after_first  # twenty more winner counts, not one more program


def test_a_long_download_is_cut_on_the_device_to_one_of_eight_lengths(compiles, monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setattr(M, "_FETCH_WHOLE_ELEMS", 1 << 10)
    arr = jnp.arange(1 << 14, dtype=jnp.int32)
    before = compiles.requests
    for length in range(1, 1 << 14, 97):
        host, nbytes = M._fetch(arr, length)
        assert np.array_equal(host, np.arange(length)) and length * 4 <= nbytes < (length + (1 << 11)) * 4
    assert compiles.requests - before <= M._FETCH_STEPS - 1  # the whole array needs none
    small, nbytes = M._fetch(arr[:1024], 5)
    assert small.tolist() == [0, 1, 2, 3, 4] and nbytes == 4096  # short enough: whole, cut on the host


def test_a_long_tiled_selection_comes_down_a_tile_at_a_time(compiles, monkeypatch):
    monkeypatch.setattr(M, "_FETCH_WHOLE_ELEMS", 1 << 10)
    registry.reset()
    before, seen = compiles.requests, set()
    for step in range(12):
        lanes, offsets = _runs_with_overlap(60_000, 3_000 * step + 7)
        lanes = np.concatenate([lanes, lanes[:30_000] + 1])
        handle = M.deduplicate_tiled_dispatch(lanes, offsets + [len(lanes)], tile_rows=50_000, compress=False)
        assert handle[0] == "batched"
        take = M.deduplicate_resolve_tiled(handle)
        assert np.array_equal(lanes[take, 0], np.unique(lanes[:, 0]))
        seen.add(len(take))
    assert len(seen) == 12
    # the kernel, then a tile's row and its cut to an eighth of the padded length: never a program a winner count
    tiles = 4
    assert compiles.requests - before <= 1 + tiles * (1 + M._FETCH_STEPS)
    counted = registry.snapshot()["merge"]
    assert counted["winners"] == sum(seen) and counted["d2h_bytes"] < counted["pad_rows"] * 4 + counted["rows_in"] * 4
    registry.reset()


@pytest.mark.parametrize("with_seq", [False, True])
def test_a_merge_longer_than_a_tile_streams_through_one_shape(compiles, monkeypatch, with_seq):
    monkeypatch.setattr(M, "_STREAM_TILE_ROWS", 1 << 12)
    rng = np.random.default_rng(8)
    registry.reset()

    def merge(n):
        keys = rng.integers(0, n // 2, n).astype(np.uint32)  # any row order, two rows a key
        lanes = np.stack([keys >> 9, keys & 511], axis=1)  # two lanes: tiles cut on the first
        seq = rng.permutation(n).astype(np.uint32)[:, None] if with_seq else None
        handle = M.deduplicate_select_async(lanes, seq, compress=False)
        assert handle[0] == "stream" and all(len(rows) <= M._STREAM_TILE_ROWS for _, rows in handle[1])
        take = M.deduplicate_resolve(handle)
        order = np.lexsort((np.arange(n) if seq is None else seq[:, 0], keys))
        last = np.r_[keys[order][1:] != keys[order][:-1], True]
        assert np.array_equal(take, order[last])  # each key's last writer, in key order
        return len(handle[1])

    tiles = merge(9_000)
    before = compiles.requests
    tiles += merge(30_000) + merge(70_000)  # the merge grows with the table: the program stays
    assert compiles.requests == before
    counted = registry.snapshot()["merge"]
    assert counted["merges"] == 3 and counted["tiles"] == tiles and counted["rows_in"] == 109_000
    assert counted["pad_rows"] == tiles * M._STREAM_TILE_ROWS - 109_000
    registry.reset()


def test_keys_that_cannot_be_cut_into_tiles_sort_whole(monkeypatch):
    monkeypatch.setattr(M, "_STREAM_TILE_ROWS", 1 << 10)
    lanes = np.stack([np.full(5_000, 7, dtype=np.uint32), np.arange(5_000, dtype=np.uint32) % 900,
                      np.arange(5_000, dtype=np.uint32) % 2], axis=1)
    lanes[0, 0] = 6  # lane 0 holds two values: one of them 4,999 rows, more than a tile
    handle = M.deduplicate_select_async(lanes, None, compress=False)
    assert handle[0] != "stream"
    assert len(M.deduplicate_resolve(handle)) == len(np.unique(lanes, axis=0))


# ---- the write path's spans and counters ----------------------------------------

@pytest.fixture(scope="module")
def ingest(tmp_path_factory):
    """Twelve traced commits into a table that has lived (run 0, compacted)."""
    tmp = tmp_path_factory.mktemp("cdc")
    table, ids = _table(tmp / "warehouse")
    stream = reference_ingest.ZipfStream(3, KEYS, CONFIG["keys"]["exponent"], BATCH)
    builder = table.new_stream_write_builder()
    writer, committer = builder.new_write(), builder.new_commit()
    registry.reset()
    distinct = 0
    with traced(tmp / "trace") as events:
        for i in range(1, 13):
            positions = stream.positions(i)
            distinct += len(np.unique(positions))
            writer.write(reference_ingest.batch_columns(ids, positions, i, SCHEMA))
            committer.commit_messages(i, writer.prepare_commit())
    writer.close()
    counters = registry.snapshot()
    registry.reset()
    return {"events": events, "counters": counters, "distinct": distinct}


WRITE_PATH_SPANS = ("write", "prepare_commit", "flush", "flush.wait", "file.write", "compact", "compact.pick",
                    "commit", "lanes.encode", "merge.dispatch", "merge.resolve", "gather", "decode.file")


@pytest.mark.parametrize("name", WRITE_PATH_SPANS)
def test_a_traced_ingest_opens_the_span(ingest, name):
    assert _named(ingest["events"], name), sorted({e[0] for e in ingest["events"]})


def test_write_path_spans_nest_and_carry_their_stats(ingest):
    events = ingest["events"]
    writes, prepares, commits = (_named(events, n) for n in ("write", "prepare_commit", "commit"))
    assert len(writes) == len(prepares) == len(commits) == 12
    for w, p, c in zip(writes, prepares, commits):
        assert w[4]["rows"] == BATCH and w[4]["buckets"] == 1 and "parent" not in w[4]
        assert w[4]["op"] > 0 and p[4]["op"] == w[4]["op"]  # prepare_commit takes up its writes' operation
        assert c[4]["op"] not in (0, w[4]["op"]) and c[4]["snapshots"] in (1, 2) and c[4]["retries"] == 0
        assert w[2] <= p[1] and p[2] <= c[1]
    ops = {w[4]["op"] for w in writes}
    assert len(ops) == 12
    # a flush is two spans: the dispatch half inside prepare_commit on the client's thread (lanes, the
    # device dedup, the gather), the landing half on the flush worker (the level-0 file)
    flushes = _named(events, "flush")
    dispatch = [f for f in flushes if "rows_in" in f[4]]
    landing = [f for f in flushes if "rows_out" in f[4]]
    assert len(dispatch) == len(landing) == 12 and all(f[4]["rows_in"] == BATCH for f in dispatch)
    assert sum(f[4]["rows_out"] for f in landing) == ingest["distinct"] and all(f[4]["files"] == 1 for f in landing)
    for f in dispatch:
        assert any(_inside(f, p) for p in prepares) and f[4]["parent"] == "prepare_commit"
        for name in ("lanes.encode", "merge.dispatch", "merge.resolve", "gather"):
            assert any(_inside(e, f) for e in _named(events, name)), name
    for f in landing:  # a pool thread: the submitter's operation and span name
        assert f[3] != prepares[0][3] and f[4]["op"] in ops and f[4]["parent"] == "prepare_commit"
        assert sum(_inside(e, f) for e in _named(events, "file.write")) == 1
    waits = _named(events, "flush.wait")
    assert len(waits) == 12 and all(any(_inside(x, p) for p in prepares) for x in waits)
    # a round of compaction follows its flush on the worker, outside the flush's span
    rounds = _named(events, "compact")
    line_of = {f[4]["op"]: f[3] for f in landing}  # a checkpoint's flush worker: a new one each prepare_commit
    assert len(rounds) == 12 and all(r[3] == line_of[r[4]["op"]] and r[4]["full"] == 0 for r in rounds)
    assert not any(_inside(r, f) for r in rounds for f in landing)
    assert all(sum(_inside(e, r) for e in _named(events, "compact.pick")) == 1 for r in rounds)
    worked = [r for r in rounds if "rows_out" in r[4]]
    assert worked and all(r[4]["runs_in"] >= 2 and 0 < r[4]["rows_out"] <= r[4]["rows_in"] and r[4]["level_out"] >= 1
                          for r in worked)
    for r in worked:
        assert any(_inside(e, r) for e in _named(events, "file.write"))
        decoded = [e for e in _named(events, "decode.file") if r[1] <= e[1] and e[2] <= r[2]]
        assert decoded and all(e[4]["op"] == r[4]["op"] for e in decoded)  # the decode pool's threads too
    files = _named(events, "file.write")
    assert all(e[4]["format"] == "parquet" and e[4]["rows"] > 0 and e[4]["bytes"] > 0 and "level" in e[4] for e in files)
    assert {e[4]["level"] for e in files if any(_inside(e, f) for f in landing)} == {0}


def test_write_path_counters_add_up(ingest):
    c = ingest["counters"]
    assert c["write"] == {"rows": 12 * BATCH, "commits": 12}
    assert c["flush"]["rows_in"] == 12 * BATCH and c["flush"]["rows_out"] == ingest["distinct"]
    assert c["flush"]["files"] == 12 and c["flush"]["bytes"] > 0
    comp = c["compaction"]
    assert comp["rounds"] == 12 and comp["duration_ms"]["total"] == 12 and 1 <= comp["compactions"] <= 12
    assert 0 < comp["rows_out"] <= comp["rows_in"] and comp["files_out"] >= comp["compactions"] and comp["bytes_out"] > 0
    events = ingest["events"]
    assert comp["rows_out"] == sum(r[4].get("rows_out", 0) for r in _named(events, "compact"))
    written = sum(e[4]["rows"] for e in _named(events, "file.write"))
    assert written == c["flush"]["rows_out"] + comp["rows_out"]
    write_amp = written / c["write"]["rows"]
    assert write_amp >= ingest["distinct"] / (12 * BATCH)  # at least the share of distinct keys a batch
    assert c["commit"]["commits"] == 12 + comp["compactions"]  # APPEND, and COMPACT where a round worked
