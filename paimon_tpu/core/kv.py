"""KeyValue batch model: rows + sequence numbers + row kinds, vectorized.

Parity: /root/reference/paimon-core/.../KeyValue.java:44 — a KeyValue is
(key, sequenceNumber, valueKind, value, level). Batch-wise that is one
ColumnBatch of the value row type plus two system vectors. The on-disk schema
is `_SEQUENCE_NUMBER BIGINT, _VALUE_KIND TINYINT, <value fields...>`
(KeyValue.java:115-120 puts key fields first; here the primary key is always a
subset of the value fields, so key columns are normally projected, not
duplicated (data-file.include-key-columns opts into the reference's
duplicated _KEY_ layout for byte-level interop) —
one less copy on the wire and on device).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import numpy as np

from ..data.batch import Column, ColumnBatch, concat_batches
from ..types import BIGINT, TINYINT, DataField, RowKind, RowType

__all__ = ["KVBatch", "SEQUENCE_FIELD_NAME", "VALUE_KIND_FIELD_NAME", "kv_disk_schema", "retracts", "LEVEL_FIELD_ID_BASE"]

SEQUENCE_FIELD_NAME = "_SEQUENCE_NUMBER"
VALUE_KIND_FIELD_NAME = "_VALUE_KIND"
# system field ids sit far above user ids (reference SpecialFields uses max-int range)
LEVEL_FIELD_ID_BASE = 2147480000


def kv_disk_schema(value_schema: RowType) -> RowType:
    fields = [
        DataField(LEVEL_FIELD_ID_BASE + 1, SEQUENCE_FIELD_NAME, BIGINT(False)),
        DataField(LEVEL_FIELD_ID_BASE + 2, VALUE_KIND_FIELD_NAME, TINYINT(False)),
        *value_schema.fields,
    ]
    return RowType(fields)


@dataclass
class KVBatch:
    """A batch of KeyValues: data (value schema), seq (int64), kind (uint8)."""

    data: ColumnBatch
    seq: np.ndarray
    kind: np.ndarray

    def __post_init__(self):
        assert len(self.seq) == len(self.kind) == self.data.num_rows
        assert self.seq.dtype == np.int64 and self.kind.dtype == np.uint8

    @property
    def num_rows(self) -> int:
        return self.data.num_rows

    def byte_size(self) -> int:
        return self.data.byte_size() + self.seq.nbytes + self.kind.nbytes

    def take(self, indices: np.ndarray) -> "KVBatch":
        return KVBatch(self.data.take(indices), self.seq.take(indices), self.kind.take(indices))

    def filter(self, mask: np.ndarray) -> "KVBatch":
        return KVBatch(self.data.filter(mask), self.seq[mask], self.kind[mask])

    def slice(self, start: int, stop: int) -> "KVBatch":
        return KVBatch(self.data.slice(start, stop), self.seq[start:stop], self.kind[start:stop])

    @staticmethod
    def concat(batches: Sequence["KVBatch"]) -> "KVBatch":
        return KVBatch(
            concat_batches([b.data for b in batches]),
            np.concatenate([b.seq for b in batches]),
            np.concatenate([b.kind for b in batches]),
        )

    @staticmethod
    def from_rows(data: ColumnBatch, start_seq: int, kinds: np.ndarray | None = None) -> "KVBatch":
        n = data.num_rows
        seq = np.arange(start_seq, start_seq + n, dtype=np.int64)
        if kinds is None:
            kinds = np.full(n, int(RowKind.INSERT), dtype=np.uint8)
        return KVBatch(data, seq, kinds)

    _KEY_FIELD_ID_OFFSET = 1_000_000_000  # keeps _KEY_ ids disjoint from value ids

    def to_disk_batch(self, key_names: "Sequence[str] | None" = None) -> ColumnBatch:
        """Attach system columns for the on-disk layout. With key_names,
        the trimmed primary key is ALSO duplicated as _KEY_<name> columns at
        the front — the reference KeyValue.schema() layout
        (KeyValue.java:115-120). Key field ids are offset so they never
        collide with the value fields' ids (the reference offsets by the max
        key id for the same reason, KeyValue.createKeyValueFields)."""
        value_schema = self.data.schema
        cols = {}
        fields = []
        if key_names:
            for name in key_names:
                f = value_schema.field(name)
                fields.append(DataField(self._KEY_FIELD_ID_OFFSET + f.id, f"_KEY_{name}", f.type))
                cols[f"_KEY_{name}"] = self.data.column(name)
        disk_schema = kv_disk_schema(value_schema)
        fields.extend(disk_schema.fields)
        cols[SEQUENCE_FIELD_NAME] = Column(self.seq)
        cols[VALUE_KIND_FIELD_NAME] = Column(self.kind.astype(np.int8))
        cols.update(self.data.columns)
        schema = RowType(tuple(fields)) if key_names else disk_schema
        return ColumnBatch(schema, cols)

    @staticmethod
    def from_disk_batch(batch: ColumnBatch, value_schema: RowType) -> "KVBatch":
        seq = batch.column(SEQUENCE_FIELD_NAME).values.astype(np.int64, copy=False)
        kind = batch.column(VALUE_KIND_FIELD_NAME).values.astype(np.uint8)
        data = ColumnBatch(value_schema, {f.name: batch.column(f.name) for f in value_schema.fields})
        return KVBatch(data, seq, kind)

    def drop_deletes(self) -> "KVBatch":
        """Batch reads strip -D/-U rows after merging (reference
        DropDeleteReader.java)."""
        drop = retracts(self.kind)
        return self.filter(~drop) if drop.any() else self


def retracts(kind: np.ndarray) -> np.ndarray:
    """Mask of the -D and -U rows: those a batch read drops."""
    return (kind == int(RowKind.DELETE)) | (kind == int(RowKind.UPDATE_BEFORE))
