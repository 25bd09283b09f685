"""Native vectorized parquet page-encode subsystem (paimon_tpu.encode).

Covers the layers and the wiring, dual to test_decode.py:
  * kernels — pack/RLE/delta/byte-array encoders pinned to the DECODE
    kernels as oracles (what one side writes the other must read back),
    plus jax-vs-numpy pack parity;
  * roundtrip — randomized native-encode → (a) native decoder and
    (b) pyarrow pq.read_table, bit-identical across encodings ×
    compressions × null-rates × page versions (long corpus sweep is
    `slow`);
  * stats — natively-written row-group statistics must prune under BOTH
    the existing arrow predicate skip and the decode pushdown gate;
  * wiring — `format.parquet.encoder = native` through table writes,
    flush + compaction (incl. the pipelined paths), per-file arrow
    fallback on unsupported shapes, encoder coverage in the data-file
    cache-key identity test, and dictionary-page pool reuse that never
    materializes a key string.
"""

import io as _io
import os

import numpy as np
import pytest

import paimon_tpu as pt
from paimon_tpu.catalog import FileSystemCatalog
from paimon_tpu.data import predicate as P
from paimon_tpu.data.batch import Column, ColumnBatch, concat_batches
from paimon_tpu.data.keys import build_string_pool, encode_key_lanes
from paimon_tpu.decode import UnsupportedParquetFeature, read_native
from paimon_tpu.decode import kernels as dk
from paimon_tpu.decode.container import (
    T_BOOLEAN,
    T_BYTE_ARRAY,
    T_INT32,
    T_INT64,
    parse_footer,
)
from paimon_tpu.encode import encode_parquet_bytes, write_native
from paimon_tpu.encode import kernels as ek
from paimon_tpu.format.parquet import ParquetFormat
from paimon_tpu.fs import LocalFileIO
from paimon_tpu.metrics import encode_metrics
from paimon_tpu.types import ArrayType

IO = LocalFileIO()

FULL_SCHEMA = pt.RowType.of(
    ("i8", pt.TINYINT()),
    ("i16", pt.SMALLINT()),
    ("i32", pt.INT()),
    ("i64", pt.BIGINT()),
    ("f32", pt.FLOAT()),
    ("f64", pt.DOUBLE()),
    ("b", pt.BOOLEAN()),
    ("s", pt.STRING()),
    ("y", pt.BYTES()),
    ("dt", pt.DATE()),
    ("ts", pt.TIMESTAMP()),
)


def _random_batch(rng, n, null_rate=0.15, schema=FULL_SCHEMA, distinct=50):
    def nullify(vals):
        if null_rate == 0:
            return list(vals)
        mask = rng.random(n) < null_rate
        return [None if m else v for v, m in zip(vals, mask)]

    gens = {
        "i8": lambda: nullify(int(x) for x in rng.integers(-128, 128, n)),
        "i16": lambda: nullify(int(x) for x in rng.integers(-1000, 1000, n)),
        "i32": lambda: nullify(int(x) for x in rng.integers(-(2**31), 2**31, n)),
        "i64": lambda: nullify(int(x) for x in rng.integers(-(2**62), 2**62, n)),
        "f32": lambda: nullify(float(x) for x in rng.integers(0, distinct, n)),
        "f64": lambda: nullify(float(x) * 0.5 for x in rng.integers(0, 10**6, n)),
        "b": lambda: nullify(bool(x) for x in rng.integers(0, 2, n)),
        "s": lambda: nullify(f"val-{int(x) % distinct:04d}" for x in rng.integers(0, 10**4, n)),
        "y": lambda: nullify(bytes([int(x) % 251]) * (int(x) % 7) for x in rng.integers(0, 255, n)),
        "dt": lambda: nullify(int(x) for x in rng.integers(0, 20000, n)),
        "ts": lambda: nullify(int(x) for x in rng.integers(0, 2**45, n)),
    }
    return ColumnBatch.from_pydict(schema, {f.name: gens[f.name]() for f in schema.fields})


def _roundtrip_both(tmp_path, batch, schema, compression="zstd", **opts):
    """Native-encode, then read back via (a) the native decoder and (b)
    pyarrow; assert both match the source bit-for-bit."""
    import pyarrow.parquet as pq

    raw = encode_parquet_bytes(batch, compression, opts)
    path = str(tmp_path / "rt.parquet")
    with open(path, "wb") as f:
        f.write(raw)
    via_arrow = ColumnBatch.from_arrow(pq.read_table(_io.BytesIO(raw)), schema)
    assert via_arrow.to_pydict() == batch.to_pydict(), "pyarrow read mismatch"
    parts = read_native(IO, path, schema)
    via_native = concat_batches(parts) if parts else ColumnBatch.empty(schema)
    assert via_native.to_pydict() == batch.to_pydict(), "native decode mismatch"
    return raw


# ---------------------------------------------------------------------------
# kernels (decode kernels are the oracles)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width", [1, 2, 3, 5, 7, 8, 12, 17, 24, 31])
def test_pack_bits_roundtrip(width, rng):
    vals = rng.integers(0, 2**width, 117).astype(np.uint64)
    packed = np.frombuffer(ek.pack_bits(vals, width), dtype=np.uint8)
    out = dk.unpack_bits(packed, width, len(vals))
    assert out.tolist() == vals.tolist()


@pytest.mark.parametrize("width", [1, 3, 8, 13, 20, 32])
def test_pack_bits_jax_matches_numpy(width, rng):
    vals = rng.integers(0, 2**min(width, 31), 200).astype(np.uint64)
    a = ek.pack_bits(vals, width)
    b = bytes(np.asarray(ek.pack_bits_jax(vals, width)))
    assert a == b


@pytest.mark.parametrize(
    "maker",
    [
        lambda rng, n: rng.integers(0, 7, n),  # random: mostly bit-packed
        lambda rng, n: np.zeros(n, dtype=np.int64),  # constant: one RLE run
        lambda rng, n: np.repeat(rng.integers(0, 5, max(n // 9, 1)), 9)[:n],  # long runs
        lambda rng, n: np.concatenate(  # mixed short + long
            [np.repeat(rng.integers(0, 3, 1), 20), rng.integers(0, 3, n)]
        )[:n],
    ],
)
@pytest.mark.parametrize("n", [1, 7, 8, 23, 1000])
def test_rle_hybrid_roundtrip(maker, n, rng):
    vals = np.ascontiguousarray(maker(rng, n), dtype=np.int64)[:n]
    width = max(ek.bit_width_for(int(vals.max())), 1) if len(vals) else 1
    enc = ek.encode_rle_hybrid(vals, width)
    out = dk.decode_rle_hybrid(enc, 0, len(enc), width, len(vals))
    assert out.tolist() == vals.tolist()


def test_rle_hybrid_width_zero_single_entry_domain():
    vals = np.zeros(37, dtype=np.int64)
    enc = ek.encode_rle_hybrid(vals, 0)
    out = dk.decode_rle_hybrid(enc, 0, len(enc), 0, 37)
    assert out.tolist() == [0] * 37


@pytest.mark.parametrize("physical", [T_INT32, T_INT64])
@pytest.mark.parametrize("n", [1, 2, 63, 64, 1023, 1024, 1025, 5000])
def test_delta_binary_packed_roundtrip(physical, n, rng):
    lo, hi = (-(2**30), 2**30) if physical == T_INT32 else (-(2**61), 2**61)
    vals = np.sort(rng.integers(lo, hi, n))
    if physical == T_INT32:
        vals = vals.astype(np.int32).astype(np.int64)
    enc = ek.encode_delta_binary_packed(vals, physical)
    out = dk.decode_delta_binary_packed(enc, 0, n, physical)
    assert out.tolist() == vals.tolist()


def test_delta_binary_packed_unsorted_and_negative(rng):
    vals = rng.integers(-(2**40), 2**40, 3000)  # delta is valid for ANY ints
    enc = ek.encode_delta_binary_packed(vals, T_INT64)
    out = dk.decode_delta_binary_packed(enc, 0, len(vals), T_INT64)
    assert out.tolist() == vals.tolist()


def test_plain_byte_array_stream_matches_decoder(rng):
    values = [f"v-{i % 13}-{'x' * (i % 5)}" for i in range(200)]
    lens, payload = ek.byte_array_parts(np.array(values, dtype=object))
    stream = ek.encode_plain_byte_array(lens, payload)
    out = dk.decode_plain(stream, 0, T_BYTE_ARRAY, len(values), utf8=True)
    assert out.tolist() == values


def test_byte_array_parts_unicode_and_nul_fallback():
    uni = np.array(["π", "日本語", "a", ""], dtype=object)
    lens, payload = ek.byte_array_parts(uni)
    assert lens.tolist() == [2, 9, 1, 0]
    assert payload == "π日本語a".encode("utf-8")
    nul = np.array(["a\x00b", "c"], dtype=object)  # S-dtype would trim: loop path
    lens, payload = ek.byte_array_parts(nul)
    assert lens.tolist() == [3, 1] and payload == b"a\x00bc"
    raw = np.array([b"ab\x00", b"", b"q"], dtype=object)  # bytes keep trailing NUL
    lens, payload = ek.byte_array_parts(raw)
    assert lens.tolist() == [3, 0, 1] and payload == b"ab\x00q"


def test_plain_boolean_roundtrip(rng):
    vals = rng.integers(0, 2, 43).astype(np.bool_)
    enc = ek.encode_plain_boolean(vals)
    out = dk.decode_plain(enc, 0, T_BOOLEAN, len(vals))
    assert out.tolist() == vals.tolist()


# ---------------------------------------------------------------------------
# file roundtrips
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("compression", ["zstd", "snappy", None])
@pytest.mark.parametrize("page_version", ["1.0", "2.0"])
def test_roundtrip_compressions_and_page_versions(tmp_path, rng, compression, page_version):
    batch = _random_batch(rng, 1200)
    _roundtrip_both(
        tmp_path,
        batch,
        FULL_SCHEMA,
        compression,
        **{"parquet.page-size": "2048", "parquet.data-page-version": page_version},
    )


@pytest.mark.parametrize("null_rate", [0.0, 0.5, 1.0])
def test_roundtrip_null_rates(tmp_path, rng, null_rate):
    batch = _random_batch(rng, 800, null_rate=null_rate)
    _roundtrip_both(tmp_path, batch, FULL_SCHEMA, **{"parquet.page-size": "1024"})


def test_roundtrip_dictionary_disabled(tmp_path, rng):
    batch = _random_batch(rng, 600)
    raw = _roundtrip_both(
        tmp_path, batch, FULL_SCHEMA, **{"parquet.enable.dictionary": "false"}
    )
    footer = parse_footer(raw)
    assert not footer.row_groups[0].columns["s"].has_dictionary


def test_roundtrip_multi_row_group_and_zstd_level(tmp_path, rng):
    batch = _random_batch(rng, 3000, null_rate=0.05)
    raw = _roundtrip_both(
        tmp_path,
        batch,
        FULL_SCHEMA,
        **{"parquet.row-group.rows": "700", "file.compression.zstd-level": "5"},
    )
    assert len(parse_footer(raw).row_groups) == 5


def test_roundtrip_empty_and_single_row(tmp_path, rng):
    schema = pt.RowType.of(("a", pt.BIGINT()), ("s", pt.STRING()))
    _roundtrip_both(tmp_path, ColumnBatch.from_pydict(schema, {"a": [3], "s": ["x"]}), schema)
    empty = ColumnBatch.from_pydict(schema, {"a": [], "s": []})
    raw = encode_parquet_bytes(empty, "zstd", {})
    import pyarrow.parquet as pq

    t = pq.read_table(_io.BytesIO(raw))
    assert t.num_rows == 0 and t.column_names == ["a", "s"]


def test_sorted_int_columns_use_delta(tmp_path):
    schema = pt.RowType.of(("k", pt.BIGINT(False)), ("d", pt.INT()))
    batch = ColumnBatch.from_pydict(
        schema, {"k": list(range(5000)), "d": sorted(int(x) % 997 for x in range(5000))}
    )
    raw = _roundtrip_both(tmp_path, batch, schema)
    from paimon_tpu.decode.container import ENC_DELTA_BINARY_PACKED

    footer = parse_footer(raw)
    for name in ("k", "d"):
        assert ENC_DELTA_BINARY_PACKED in footer.row_groups[0].columns[name].encodings


@pytest.mark.slow
@pytest.mark.parametrize("seed", range(24))
def test_roundtrip_corpus_sweep(tmp_path, seed):
    """Wide seeded sweep (dual of the PR 2 decode corpus): every seed picks
    its own size / null rate / distinct count / page size / compression /
    page version, and must round-trip bit-identically through BOTH readers."""
    rng = np.random.default_rng(9000 + seed)
    n = int(rng.integers(1, 4000))
    null_rate = float(rng.choice([0.0, 0.05, 0.3, 0.9]))
    distinct = int(rng.choice([1, 3, 50, 5000]))
    batch = _random_batch(rng, n, null_rate=null_rate, distinct=distinct)
    opts = {
        "parquet.page-size": str(int(rng.choice([512, 2048, 65536]))),
        "parquet.data-page-version": str(rng.choice(["1.0", "2.0"])),
        "parquet.enable.dictionary": str(rng.choice(["true", "false"])),
    }
    compression = rng.choice(["zstd", "snappy", None])
    _roundtrip_both(tmp_path, batch, FULL_SCHEMA, compression, **opts)


# ---------------------------------------------------------------------------
# dictionary pool reuse (the merge-path fast lane)
# ---------------------------------------------------------------------------


def test_dict_cache_pool_reuse_never_touches_key_strings(tmp_path, monkeypatch):
    schema = pt.RowType.of(("k", pt.STRING(False)), ("v", pt.BIGINT()))
    keys = [f"key-{i:05d}" for i in range(2000)]
    batch = ColumnBatch.from_pydict(schema, {"k": keys, "v": list(range(2000))})
    kcol = batch.column("k")
    pool = build_string_pool([kcol.values])
    encode_key_lanes(batch, ["k"], {"k": pool})
    assert kcol.dict_cache is not None

    touched = []
    orig = Column.values
    monkeypatch.setattr(
        Column, "values", property(lambda self: touched.append(self) or orig.fget(self))
    )
    g = encode_metrics()
    d0 = g.counter("dict_pages").count
    raw = encode_parquet_bytes(batch, "zstd", {}, metrics=g)
    assert kcol not in touched, "pool-reuse encode must not rematerialize key strings"
    assert g.counter("dict_pages").count == d0 + 1
    monkeypatch.undo()

    footer = parse_footer(raw)
    assert footer.row_groups[0].columns["k"].has_dictionary
    import pyarrow.parquet as pq

    assert pq.read_table(_io.BytesIO(raw)).column("k").to_pylist() == keys


def test_dict_cache_survives_structural_ops():
    schema = pt.RowType.of(("k", pt.STRING(False)),)
    batch = ColumnBatch.from_pydict(schema, {"k": [f"a{i % 7}" for i in range(50)]})
    col = batch.column("k")
    pool = build_string_pool([col.values])
    encode_key_lanes(batch, ["k"], {"k": pool})
    taken = col.take(np.array([4, 9, 11]))
    sliced = col.slice(5, 20)
    filtered = col.filter(np.arange(50) % 2 == 0)
    for derived in (taken, sliced, filtered):
        dpool, codes = derived.dict_cache
        assert dpool is pool
        assert (dpool[codes] == derived.values).all()
    assert Column.concat([taken, sliced]).dict_cache is None  # pools differ per merge


# ---------------------------------------------------------------------------
# statistics / pruning
# ---------------------------------------------------------------------------


def test_native_stats_prune_row_groups_under_arrow_predicate_skip(tmp_path):
    schema = pt.RowType.of(("k", pt.BIGINT()), ("v", pt.DOUBLE()))
    batch = ColumnBatch.from_pydict(
        schema, {"k": list(range(10000)), "v": [float(i) for i in range(10000)]}
    )
    path = str(tmp_path / "stats.parquet")
    write_native(IO, path, batch, "zstd", {"parquet.row-group.rows": "1000"})
    pred = P.PredicateBuilder(schema).between("k", 2500, 2600)
    # the EXISTING arrow read path (format/parquet.py::_row_group_stats)
    # must trust the native writer's statistics and open only one group
    parts = list(ParquetFormat().read(IO, path, schema, predicate=pred))
    assert sum(p.num_rows for p in parts) == 1000
    # and the decode subsystem's chunk-stats gate must prune identically
    native = concat_batches(read_native(IO, path, schema, predicate=pred))
    assert native.num_rows == 1000
    assert native.column("k").values.min() == 2000


def test_native_string_stats_prune(tmp_path):
    schema = pt.RowType.of(("s", pt.STRING()), ("v", pt.BIGINT()))
    batch = ColumnBatch.from_pydict(
        schema,
        {"s": [f"g{i // 1000}-{i:05d}" for i in range(4000)], "v": list(range(4000))},
    )
    path = str(tmp_path / "sstats.parquet")
    write_native(IO, path, batch, "zstd", {"parquet.row-group.rows": "1000"})
    pred = P.PredicateBuilder(schema).equal("s", "g2-02042")
    parts = list(ParquetFormat().read(IO, path, schema, predicate=pred))
    assert sum(p.num_rows for p in parts) == 1000
    rows = concat_batches(parts)
    assert rows.column("v").values.min() == 2000


def test_long_string_stats_are_omitted_not_wrong(tmp_path):
    schema = pt.RowType.of(("s", pt.STRING()),)
    batch = ColumnBatch.from_pydict(schema, {"s": ["z" * 100, "a" * 100]})
    path = str(tmp_path / "long.parquet")
    write_native(IO, path, batch, None, {})
    footer = parse_footer(IO.read_bytes(path))
    st = footer.row_groups[0].columns["s"].stats
    assert 5 not in st and 6 not in st  # >=64-byte min/max omitted (trust limit)
    pred = P.PredicateBuilder(schema).equal("s", "z" * 100)
    got = concat_batches(list(ParquetFormat().read(IO, path, schema, predicate=pred)))
    assert got.num_rows == 2  # nothing wrongly pruned


# ---------------------------------------------------------------------------
# wiring: format option, fallback, table writes, cache identity
# ---------------------------------------------------------------------------

TBL_SCHEMA = pt.RowType.of(("k", pt.BIGINT()), ("s", pt.STRING()), ("v", pt.DOUBLE()))


def _write_table(table, keys, step):
    wb = table.new_batch_write_builder()
    w = wb.new_write()
    w.write(
        {
            "k": list(keys),
            "s": [f"s{int(k) % 5}" for k in keys],
            "v": [float(step) + float(k) / 1000 for k in keys],
        }
    )
    wb.new_commit().commit(w.prepare_commit())


def _read_rows(table, predicate=None):
    rb = table.new_read_builder()
    if predicate is not None:
        rb = rb.with_filter(predicate)
    return sorted(rb.new_read().read_all(rb.new_scan().plan()).to_pylist())


def test_format_write_routes_through_native_encoder(tmp_path, rng):
    batch = _random_batch(rng, 500)
    path = str(tmp_path / "fmt.parquet")
    g = encode_metrics()
    n0, f0 = g.counter("files_native").count, g.counter("files_fallback").count
    ParquetFormat().write(
        IO, path, batch, format_options={"format.parquet.encoder": "native"}
    )
    assert g.counter("files_native").count == n0 + 1
    assert g.counter("files_fallback").count == f0
    got = concat_batches(list(ParquetFormat().read(IO, path, FULL_SCHEMA)))
    assert got.to_pydict() == batch.to_pydict()


def test_unsupported_shapes_fall_back_per_file(tmp_path):
    schema = pt.RowType.of(("k", pt.BIGINT()), ("arr", ArrayType(pt.INT())))
    nested = ColumnBatch.from_pydict(schema, {"k": [1, 2], "arr": [[1], [2, 3]]})
    flat = ColumnBatch.from_pydict(
        pt.RowType.of(("k", pt.BIGINT())), {"k": [1, 2, 3]}
    )
    fmt = ParquetFormat()
    opts = {"format.parquet.encoder": "native"}
    g = encode_metrics()
    n0, f0 = g.counter("files_native").count, g.counter("files_fallback").count
    fmt.write(IO, str(tmp_path / "nested.parquet"), nested, format_options=opts)
    assert g.counter("files_fallback").count == f0 + 1, "nested must fall back"
    # fallback is per FILE: the next flat write on the same format instance
    # still encodes natively
    fmt.write(IO, str(tmp_path / "flat.parquet"), flat, format_options=opts)
    assert g.counter("files_native").count == n0 + 1
    got = concat_batches(list(ParquetFormat().read(IO, str(tmp_path / "nested.parquet"), schema)))
    assert got.to_pydict() == nested.to_pydict()


def test_native_encoder_through_table_write_and_compaction(tmp_warehouse):
    cat = FileSystemCatalog(tmp_warehouse, commit_user="c")
    opts = {
        "bucket": "1",
        "num-sorted-run.compaction-trigger": "2",
        "cache.data-file.max-memory-size": "0 b",
    }
    arrow_t = cat.create_table("db.enc_a", TBL_SCHEMA, primary_keys=["k"], options=opts)
    native_t = cat.create_table(
        "db.enc_n",
        TBL_SCHEMA,
        primary_keys=["k"],
        options={**opts, "format.parquet.encoder": "native"},
    )
    g = encode_metrics()
    n0 = g.counter("files_native").count
    for step in range(4):  # trips compaction: rewrites encode natively too
        _write_table(arrow_t, range(step, 40 + step), step)
        _write_table(native_t, range(step, 40 + step), step)
    assert g.counter("files_native").count > n0
    assert _read_rows(native_t) == _read_rows(arrow_t)
    # natively-written files must ALSO decode natively (full dual stack)
    assert _read_rows(native_t.copy({"format.parquet.decoder": "native"})) == _read_rows(arrow_t)


@pytest.mark.parametrize("fmt_opts", [
    {"parquet.data-page-version": "2.0"},
    {"file.compression": "snappy"},
    {"parquet.enable.dictionary": "false", "parquet.page-size": "1024"},
])
def test_native_encoder_table_option_matrix(tmp_warehouse, fmt_opts):
    cat = FileSystemCatalog(tmp_warehouse, commit_user="c")
    name = "db.m" + str(abs(hash(tuple(sorted(fmt_opts)))) % 10**6)
    t = cat.create_table(
        name,
        TBL_SCHEMA,
        primary_keys=["k"],
        options={
            "bucket": "1",
            "format.parquet.encoder": "native",
            "cache.data-file.max-memory-size": "0 b",
            **fmt_opts,
        },
    )
    for step in range(2):
        _write_table(t, range(30), step)
    rows = _read_rows(t)
    assert len(rows) == 30
    assert all(r[2] == pytest.approx(1.0 + r[0] / 1000) for r in rows)


def test_encoder_identity_in_data_file_cache_key(tmp_warehouse):
    """A natively-written file must not alias an arrow-written one in the
    decoded data-file cache: a table that toggles the encoder between
    commits keeps one cache entry per file and reads stay correct."""
    from paimon_tpu.utils.cache import data_file_cache

    cat = FileSystemCatalog(tmp_warehouse, commit_user="c")
    t = cat.create_table(
        "db.enc_ck",
        TBL_SCHEMA,
        primary_keys=["k"],
        options={"bucket": "1", "write-only": "true", "cache.data-file.max-memory-size": "64 mb"},
    )
    _write_table(t, range(30), 0)  # arrow-encoded file
    native_view = t.copy({"format.parquet.encoder": "native"})
    _write_table(native_view, range(20, 50), 1)  # native-encoded file
    expect = _read_rows(t.copy({"cache.data-file.max-memory-size": "0 b"}))
    before = len(data_file_cache())
    assert _read_rows(t) == expect
    after_first = len(data_file_cache())
    assert after_first > before, "both files must enter the cache"
    assert _read_rows(t) == expect  # warm hit: same entries, same rows
    assert len(data_file_cache()) == after_first, "re-read must not mint new entries"


# ---------------------------------------------------------------------------
# pipelined flush / compaction and faults (verify.sh stages run these)
# ---------------------------------------------------------------------------


def test_native_encoder_pipelined_flush_and_compaction(tmp_warehouse):
    """scripts/verify.sh pipeline: the PR 4 pipelined flush offload and the
    pipelined compaction rewrite must route their encodes through the native
    encoder when enabled — bit-identical to the arrow-encoded table."""
    cat = FileSystemCatalog(tmp_warehouse, commit_user="c")
    base = {
        "bucket": "1",
        "scan.prefetch-splits": "2",
        "num-sorted-run.compaction-trigger": "2",
        "write-buffer-rows": "64",  # force mid-commit auto-flushes (offloaded)
        "cache.data-file.max-memory-size": "0 b",
    }
    arrow_t = cat.create_table("db.pipe_a", TBL_SCHEMA, primary_keys=["k"], options=base)
    native_t = cat.create_table(
        "db.pipe_n",
        TBL_SCHEMA,
        primary_keys=["k"],
        options={**base, "format.parquet.encoder": "native"},
    )
    g = encode_metrics()
    n0 = g.counter("files_native").count
    for step in range(3):
        _write_table(arrow_t, range(step * 30, step * 30 + 150), step)
        _write_table(native_t, range(step * 30, step * 30 + 150), step)
    assert g.counter("files_native").count > n0, "pipelined flush must encode natively"
    assert _read_rows(native_t) == _read_rows(arrow_t)


def test_native_encoder_under_transient_faults(tmp_path):
    """scripts/verify.sh faults: native-encoded writes behind the retry
    stack — scripted write faults are absorbed, commits land, reads match."""
    from paimon_tpu.core.commit import ManifestCommittable
    from paimon_tpu.core.schema import SchemaManager
    from paimon_tpu.core.store import KeyValueFileStore
    from paimon_tpu.fs import get_file_io
    from paimon_tpu.fs.testing import FailingFileIO, FaultRule

    domain = "encfault"
    FailingFileIO.reset(domain, 0, 0)
    io = get_file_io(f"fail://{domain}/x")
    path = f"fail://{domain}{tmp_path}/table"
    schema = pt.RowType.of(("k", pt.BIGINT()), ("v", pt.DOUBLE()))
    ts = SchemaManager(io, path).create_table(
        schema,
        primary_keys=["k"],
        options={
            "bucket": "1",
            "format.parquet.encoder": "native",
            "fs.retry.initial-backoff": "1 ms",
            "cache.data-file.max-memory-size": "0 b",
        },
    )
    store = KeyValueFileStore(io, path, ts, commit_user="enc")
    g = encode_metrics()
    n0 = g.counter("files_native").count
    oracle = {}
    for round_ in range(1, 4):
        # fail the first data-file write of the round once: the retry layer
        # must re-drive the native encoder's write_bytes transparently
        FailingFileIO.schedule(domain, FaultRule(op="write", path="/bucket-0/data-"))
        ks = list(range(round_ * 3, round_ * 3 + 10))
        vs = [float(k) * 0.5 + round_ for k in ks]
        w = store.new_writer((), 0)
        w.write(ColumnBatch.from_pydict(store.value_schema, {"k": ks, "v": vs}))
        msg = w.prepare_commit()
        assert store.new_commit().commit(ManifestCommittable(round_, messages=[msg]))
        oracle.update(dict(zip(ks, vs)))
    FailingFileIO.reset(domain, 0, 0)
    assert g.counter("files_native").count > n0
    batch = store.read_bucket((), 0, store.restore_files((), 0))
    got = {r[0]: r[1] for r in batch.to_pylist()}
    assert got == oracle


# ---------------------------------------------------------------------------
# satellites: to_arrow nested fast path, metrics group
# ---------------------------------------------------------------------------


def test_to_arrow_nested_fast_path_parity():
    schema = pt.RowType.of(("k", pt.BIGINT()), ("arr", ArrayType(pt.INT())))
    no_nulls = ColumnBatch.from_pydict(schema, {"k": [1, 2, 3], "arr": [[1], [2, 3], []]})
    t = no_nulls.to_arrow()
    assert t.column("arr").to_pylist() == [[1], [2, 3], []]
    with_nulls = ColumnBatch.from_pydict(schema, {"k": [1, 2], "arr": [[7], None]})
    t2 = with_nulls.to_arrow()
    assert t2.column("arr").to_pylist() == [[7], None]
    # the masked path must not mutate the source column in place
    assert with_nulls.column("arr").values[1] is None or with_nulls.column("arr").values[0] == [7]


def test_encode_metric_group_members(tmp_path, rng):
    g = encode_metrics()
    before = {
        k: g.counter(k).count
        for k in ("pages_written", "bytes_written", "dict_pages", "files_native")
    }
    batch = _random_batch(rng, 400)
    write_native(IO, str(tmp_path / "m.parquet"), batch, "zstd", {"parquet.page-size": "1024"})
    assert g.counter("files_native").count == before["files_native"] + 1
    assert g.counter("pages_written").count > before["pages_written"]
    assert g.counter("bytes_written").count > before["bytes_written"]
    assert g.counter("dict_pages").count > before["dict_pages"]
    assert g.histogram("stats_ms").count > 0


def test_env_override_forces_native(tmp_path, rng, monkeypatch):
    monkeypatch.setenv("PAIMON_TPU_PARQUET_ENCODER", "native")
    g = encode_metrics()
    n0 = g.counter("files_native").count
    batch = _random_batch(rng, 100)
    ParquetFormat().write(IO, str(tmp_path / "env.parquet"), batch)  # no option set
    assert g.counter("files_native").count == n0 + 1


# ---------------------------------------------------------------------------
# numeric dictionary route (ISSUE 13, declared PR 12 follow-up)
# ---------------------------------------------------------------------------


def test_numeric_dictionary_route_roundtrip(tmp_path):
    """Low-cardinality int32/int64/date columns dictionary-encode natively:
    dict page + RLE_DICTIONARY codes, read back bit-identically by the
    native decoder, pyarrow AND the code-domain reader (so native-written
    files join fixed-width code-domain lookups/joins)."""
    import pyarrow.parquet as pq

    rng = np.random.default_rng(3)
    n = 2000
    schema = pt.RowType.of(
        ("k", pt.BIGINT()), ("c32", pt.INT()), ("c64", pt.BIGINT()), ("d", pt.DATE())
    )
    batch = ColumnBatch.from_pydict(
        schema,
        {
            "k": np.arange(n, dtype=np.int64),  # monotone: stays DELTA
            "c32": rng.integers(-50, 50, n).astype(np.int32),
            "c64": (rng.integers(0, 9, n) * 10_000).astype(np.int64),
            "d": rng.integers(18000, 18020, n).astype(np.int32),
        },
    )
    g = encode_metrics()
    d0 = g.counter("dict_pages").count
    path = str(tmp_path / "nd.parquet")
    write_native(IO, path, batch, "zstd", {})
    assert g.counter("dict_pages").count >= d0 + 3  # c32, c64, d
    # native decode parity
    got = concat_batches(read_native(IO, path, schema))
    for c in schema.field_names:
        assert np.array_equal(got.column(c).values, batch.column(c).values), c
    # pyarrow readback parity
    at = pq.read_table(path)
    for c in ("c32", "c64", "d"):
        assert at.column(c).to_pylist() == batch.column(c).values.tolist()
    # code-domain read: the fixed-width dict chunks come back code-backed
    coded = concat_batches(read_native(IO, path, schema, dict_domain=True))
    assert coded.column("c32").is_code_backed
    assert np.array_equal(coded.column("c32").values, batch.column("c32").values)
    pool, codes = coded.column("c64").dict_cache
    assert pool.dtype == np.int64 and np.array_equal(pool[codes], batch.column("c64").values)


def test_numeric_dictionary_route_skips_high_cardinality(tmp_path):
    """Unique-ish int columns must stay PLAIN/DELTA — a dictionary the size
    of the data would only add a page."""
    rng = np.random.default_rng(4)
    n = 1000
    schema = pt.RowType.of(("k", pt.BIGINT()), ("u", pt.BIGINT()))
    batch = ColumnBatch.from_pydict(
        schema,
        {"k": np.arange(n, dtype=np.int64), "u": rng.permutation(n).astype(np.int64) * 7 + 1},
    )
    g = encode_metrics()
    d0 = g.counter("dict_pages").count
    path = str(tmp_path / "hc.parquet")
    write_native(IO, path, batch, "none", {})
    assert g.counter("dict_pages").count == d0
    got = concat_batches(read_native(IO, path, schema))
    assert np.array_equal(got.column("u").values, batch.column("u").values)


def test_numeric_dictionary_route_with_nulls(tmp_path):
    rng = np.random.default_rng(5)
    n = 1500
    schema = pt.RowType.of(("k", pt.BIGINT()), ("c", pt.INT()))
    vals = rng.integers(0, 12, n).astype(np.int32)
    validity = rng.random(n) > 0.3
    col = Column(vals.copy(), validity.copy())
    batch = ColumnBatch(schema, {"k": Column(np.arange(n, dtype=np.int64)), "c": col})
    path = str(tmp_path / "nn.parquet")
    write_native(IO, path, batch, "zstd", {})
    got = concat_batches(read_native(IO, path, schema))
    gc = got.column("c")
    assert np.array_equal(gc.valid_mask(), validity)
    assert np.array_equal(gc.values[validity], vals[validity])
