"""ORC read/write over pyarrow, with stripe-statistics pruning.

Parity: /root/reference/paimon-format/.../orc/OrcReaderFactory.java (batch
decode into column vectors, SearchArgument pushdown into the ORC reader).
pyarrow decodes stripes but exposes no stripe statistics, so orc_meta.py
reads them straight from the file tail; Predicate.test_stats then skips
whole stripes before any decode — the same evaluator used for file- and
parquet-row-group-level pruning.
"""

from __future__ import annotations

from typing import Iterator, Sequence

from ..data.batch import ColumnBatch
from ..data.predicate import Predicate
from ..fs import FileIO
from ..metrics import span
from ..types import RowType
from . import FileFormat, from_arrow, library_call, put, register_format, to_arrow


class OrcFormat(FileFormat):
    identifier = "orc"

    def write(
        self,
        file_io: FileIO,
        path: str,
        batch: ColumnBatch,
        compression: str = "zstd",
        format_options: dict | None = None,
    ) -> None:
        import io as _io

        import pyarrow.orc as po

        table = to_arrow(batch)
        buf = _io.BytesIO()
        opts = format_options or {}
        stripe_size = int(opts.get("orc.stripe.size", opts.get("file.block-size", 64 << 20)))
        with span("encode.format", rows=batch.num_rows):
            po.write_table(table, buf, compression=compression, stripe_size=stripe_size)
        put(file_io, path, buf, batch.num_rows)

    def read(
        self,
        file_io: FileIO,
        path: str,
        schema: RowType,
        projection: Sequence[str] | None = None,
        predicate: Predicate | None = None,
    ) -> Iterator[ColumnBatch]:
        import pyarrow as pa
        import pyarrow.orc as po

        cols = list(projection) if projection is not None else schema.field_names
        read_schema = schema.project(cols)
        # real OS path -> pyarrow's own C++ IO (no Python-file shim; see
        # FileIO.local_path); stream path only for non-local/intercepted IO
        lp = file_io.local_path(path)
        f = open(lp, "rb") if lp is not None else file_io.open_input(path)
        try:
            tail = None
            if predicate is not None:
                from ..metrics import registry

                try:
                    tail = _read_tail_from(f)
                except Exception:  # malformed/foreign tail: read everything
                    tail = None
                f.seek(0)
            with library_call("open", len(cols), rows=0):
                of = po.ORCFile(lp if lp is not None else f)
            for stripe in range(of.nstripes):
                if tail is not None and stripe < tail.nstripes:
                    if not predicate.test_stats(tail.stripe_stats(stripe)):
                        registry.group("scan").counter("orc_stripes_skipped").inc()
                        continue
                with library_call("stripe", len(cols)) as sp:
                    table = of.read_stripe(stripe, columns=cols)
                    if isinstance(table, pa.RecordBatch):
                        table = pa.Table.from_batches([table])
                    sp.add(rows=table.num_rows)
                if table.num_rows:
                    yield from_arrow(table, read_schema)
        finally:
            f.close()


def _read_tail_from(f, first_guess: int = 256 * 1024):
    """Parse the OrcTail from just the trailing region holding
    postscript+footer+metadata — decode stays stripe-by-stripe on the file
    handle, memory stays bounded, and the tail parses exactly once."""
    from .orc_meta import read_tail

    size = f.seek(0, 2)
    take = min(size, first_guess)
    f.seek(size - take)
    data = f.read(take)
    try:
        return read_tail(data)
    except ValueError:  # tail larger than the guess: take the whole file
        f.seek(0)
        return read_tail(f.read())


register_format("orc", OrcFormat)
