"""Native vectorized Parquet page-encode subsystem — the write-side dual of
paimon_tpu.decode.

Takes merge-kernel output (padded columnar ndarrays, string keys already
dictionary ranks against a sorted pool) to parquet file bytes without
routing through ColumnBatch.to_arrow + pq.write_table. The layers:

  decode/thrift.py — compact-protocol writer (build_struct) shared with the
                     parser, for page headers and the footer
  kernels.py       — vectorized encoders: LSB bit-pack, RLE/bit-packed
                     hybrid, PLAIN (incl. booleans + byte arrays),
                     DELTA_BINARY_PACKED, validity → def-levels (numpy
                     engine + jittable JAX twin pack_bits_jax)
  pages.py         — column → dictionary page + data pages + chunk stats;
                     consumes the merge path's string pools/rank vectors
                     directly (Column.dict_cache) so no string object
                     materializes between merge and file bytes
  writer.py        — chunk/row-group/footer assembly with vectorized
                     min/max statistics and TYPE_DEFINED_ORDER column
                     orders, so both `_row_group_stats` pruning and the
                     decode subsystem's chunk-stats gate keep working

Entry point `write_native` mirrors `ParquetFormat.write`'s arrow semantics:
same schema annotations (UTF8 / INT_8 / INT_16), OPTIONAL leaves, same
writer knobs (`parquet.page-size`, `parquet.data-page-version`,
`parquet.row-group.rows`, `file.block-size`, `parquet.enable.dictionary`,
`file.compression.zstd-level`). Batches needing features outside the
native envelope raise UnsupportedParquetFeature BEFORE any byte is written
and the format falls back to the arrow writer per file (counter
encode.files_fallback).

Surfaced behind the FileFormat registry as table option
`format.parquet.encoder = arrow | native` (default arrow).
"""

from __future__ import annotations

from ..data.batch import ColumnBatch
from ..decode.container import UnsupportedParquetFeature
from ..fs import FileIO
from ..metrics import encode_metrics, span
from .writer import encode_parquet_bytes

__all__ = ["write_native", "encode_parquet_bytes", "UnsupportedParquetFeature"]

# process-lifetime counter, deliberately OUTSIDE the metrics registry so
# registry.reset() in tests cannot zero it: scripts/verify.sh stages that
# force PAIMON_TPU_PARQUET_ENCODER=native assert at session end that the
# native encoder actually ran (conftest._forced_encoder_coverage)
_files_native_total = 0


def files_native_total() -> int:
    return _files_native_total


def write_native(
    file_io: FileIO,
    path: str,
    batch: ColumnBatch,
    compression: str | None = "zstd",
    format_options: dict | None = None,
) -> None:
    """Encode one batch natively and write it. Raises
    UnsupportedParquetFeature (without writing anything) when the batch is
    outside the native envelope — the caller falls back to arrow per file."""
    global _files_native_total
    metrics = encode_metrics()
    with span("encode.format", rows=batch.num_rows):
        data = encode_parquet_bytes(batch, compression, format_options, metrics=metrics)
    with span("encode.put", rows=batch.num_rows, bytes=len(data)):
        file_io.write_bytes(path, data)
    metrics.counter("files_native").inc()
    metrics.counter("bytes_written").inc(len(data))
    _files_native_total += 1
