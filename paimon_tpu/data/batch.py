"""Columnar batch model.

Replaces the reference's row + columnar-batch duo (BinaryRow,
VectorizedColumnBatch — /root/reference/paimon-common/.../data/columnar/
VectorizedColumnBatch.java:37) with a single structure: a ColumnBatch is a
RowType plus one dense numpy vector (and optional validity bitmap) per field.

Design rules that keep this TPU-friendly:
  * fixed-width columns are contiguous numpy arrays of the type's dtype —
    they move to device memory with zero transformation;
  * validity is a separate bool vector (never sentinel values), so device
    kernels can consume it as a mask lane;
  * variable-width (string/bytes) columns are object arrays host-side and are
    never shipped to device — kernels see them only as dictionary ranks
    (see paimon_tpu.data.keys) and rematerialize by gather on host;
  * all structural ops (take/slice/concat) are O(columns) numpy calls, no
    Python-per-row loops.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Iterable, Mapping, Sequence

import numpy as np

# pyarrow is initialised HERE, by whichever thread imports the package, and
# not lazily by the first worker that touches an arrow array: its default
# (mimalloc) memory pool is bound to the thread that first imports pyarrow,
# and once that thread exits — a paimon-flush / paimon-decode pool worker
# does — the next `pa.array(..., from_pandas=True)` on any other thread
# segfaults (pyarrow 25.0.0; ARROW_DEFAULT_MEMORY_POOL=system hides it).
# The function-local `import pyarrow` lines below are then just name lookups.
import pyarrow  # noqa: F401

from ..types import DataField, DataType, RowType, TypeRoot

__all__ = ["BatchSink", "Column", "ColumnBatch", "PartsTake", "concat_batches"]

_STRING_ROOTS = (TypeRoot.CHAR, TypeRoot.VARCHAR, TypeRoot.BINARY, TypeRoot.VARBINARY)


class PartsTake:
    """Where the rows that `take` names lie in the parts: `take` indexes the
    concatenation of parts whose row offsets are `offsets` (len(parts) + 1
    of them, ascending from 0), in any order. Computed once a gather and
    shared by every column (Column.take_from_parts).

    `picks` is a list of (part, positions in the output, rows of the part),
    both intp and the positions ascending. The output is cut into stretches
    of CHUNK rows and each stretch into one pick a part found there, so that
    a pick's scatter stays inside a cache-sized piece of the output and its
    temporaries inside blocks the allocator hands out again. `map_fn(fn,
    items)` runs the stretches (the read path gives its pool's map)."""

    CHUNK = 1 << 18

    __slots__ = ("take", "offsets", "picks")

    def __init__(self, offsets: Sequence[int], take: np.ndarray, map_fn=map):
        self.take = take
        self.offsets = np.asarray(offsets, dtype=np.intp)
        self.picks = [p for ps in map_fn(self._picks_of, range(0, len(take), self.CHUNK)) for p in ps]

    def _picks_of(self, start: int) -> list[tuple[int, np.ndarray, np.ndarray]]:
        idx = self.take[start : start + self.CHUNK]
        if idx.min() < 0 or idx.max() >= self.offsets[-1]:
            raise IndexError(f"take index out of bounds for {self.offsets[-1]} rows")
        part = np.searchsorted(self.offsets[1:], idx, side="right")
        # the stretch's positions grouped by part: stable, so they ascend
        # within a part (a radix sort where the parts number under 65536)
        order = np.argsort(part.astype(np.min_scalar_type(len(self.offsets)), copy=False), kind="stable")
        counts = np.bincount(part, minlength=len(self.offsets) - 1)
        picks, at = [], 0
        for f in np.flatnonzero(counts):
            sel = order[at : at + counts[f]]
            at += counts[f]
            local = idx.take(sel).astype(np.intp, copy=False)
            local -= self.offsets[f]
            picks.append((int(f), sel + start, local))
        return picks


class Column:
    """values + optional validity (True = present). validity None = all valid.

    String/bytes columns may additionally be backed by a pyarrow array
    (`arrow`): structural ops (take/slice/filter/concat) then run in arrow's
    C++ and the object ndarray materializes lazily only when `.values` is
    actually touched (predicates, key pools, python access; counted in
    dict{arrow_expanded}).

    `dict_cache` is an optional (sorted pool, uint32 ranks) pair attached by
    the key-lane encoder (data/keys.py): the ranks ARE exact dictionary
    codes against the pool, so the native parquet encoder emits dictionary
    pages without ever touching a string object. Structural ops transform
    the ranks alongside the values.

    A column may also be CODE-BACKED (`from_codes`): no values, no arrow —
    only the (pool, codes) pair, produced by the code-domain reader mode
    (merge.dict-domain). Structural ops then touch only the uint32 codes;
    concat unifies the input pools in the code domain (ops.dicts); the
    object ndarray materializes lazily only when `.values` is actually
    needed (counted in dict{fallback_expanded}). Non-code-backed concat
    drops the cache (pools differ per input)."""

    __slots__ = ("_values", "validity", "arrow", "_len", "dict_cache")

    def __init__(self, values: np.ndarray | None = None, validity: np.ndarray | None = None, arrow=None):
        assert values is not None or arrow is not None
        self._values = values
        self.arrow = arrow
        self.dict_cache = None
        self._len = len(values) if values is not None else len(arrow)
        if validity is not None:
            assert validity.dtype == np.bool_
            assert len(validity) == self._len
            if bool(validity.all()):
                validity = None
        self.validity = validity

    @staticmethod
    def from_codes(pool: np.ndarray, codes: np.ndarray, validity: np.ndarray | None = None) -> "Column":
        """Code-backed column over a sorted dictionary pool. Codes are
        full-length uint32 ranks into the pool; values at invalid slots are
        meaningless by contract (conventionally 0)."""
        col = Column.__new__(Column)
        col._values = None
        col.arrow = None
        col.dict_cache = (pool, codes.astype(np.uint32, copy=False))
        col._len = len(codes)
        if validity is not None:
            assert validity.dtype == np.bool_ and len(validity) == col._len
            if bool(validity.all()):
                validity = None
        col.validity = validity
        return col

    @property
    def is_code_backed(self) -> bool:
        return self._values is None and self.arrow is None

    def _with_cache(self, out: "Column", transform) -> "Column":
        if self.dict_cache is not None:
            pool, codes = self.dict_cache
            out.dict_cache = (pool, transform(codes))
        return out

    @property
    def values(self) -> np.ndarray:
        if self._values is None:
            from ..metrics import dict_metrics

            if self.arrow is None:
                # code-backed: expand pool[codes] on first python-level
                # access. Object pools fill nulls with None (matching the
                # expanded decode); fixed-width pools fill with the zero
                # sentinel, exactly like decode_chunk's null fill
                pool, codes = self.dict_cache
                if len(pool):
                    v = pool.take(np.minimum(codes, len(pool) - 1))
                    if v is pool or not v.flags.writeable:
                        v = v.copy()
                else:
                    v = np.empty(self._len, dtype=pool.dtype)
                    if pool.dtype.kind in "biufM":
                        v[:] = 0
                if self.validity is not None:
                    v[~self.validity] = None if pool.dtype == np.dtype(object) else 0
                dict_metrics().counter("fallback_expanded").inc(self._len)
                self._values = v
                return v
            arr = self.arrow
            v = arr.to_numpy(zero_copy_only=False)
            if v.dtype != np.dtype(object):
                v = v.astype(object)
            dict_metrics().counter("arrow_expanded").inc(self._len)
            self._values = v
        return self._values

    @property
    def dtype(self) -> np.dtype:
        """The dtype of `.values`, read without building them: an
        arrow-backed column's is object, a code-backed one's its pool's."""
        if self._values is not None:
            return self._values.dtype
        if self.arrow is not None:
            return np.dtype(object)
        return self.dict_cache[0].dtype

    def value_at(self, i: int):
        """One python value without materializing the whole column (file
        min/max key extraction over code-backed/arrow columns)."""
        if self.validity is not None and not self.validity[i]:
            return None
        if self._values is None:
            if self.arrow is None:
                pool, codes = self.dict_cache
                return pool[int(codes[i])]
            return self.arrow[int(i)].as_py()
        return self._values[i]

    def byte_size(self) -> int:
        """Approximate heap footprint — the currency of write-buffer budgets
        (reference MemorySegmentPool accounts bytes, not rows)."""
        if self.arrow is not None:
            total = self.arrow.nbytes
        elif self._values is None:
            # code-backed: codes + a sampled estimate of the pool payload
            pool, codes = self.dict_cache
            sample = pool[:1024]
            payload = sum(len(x) if isinstance(x, (str, bytes)) else 16 for x in sample if x is not None)
            total = codes.nbytes + int(len(pool) * (8 + payload / max(len(sample), 1)))
        elif self._values.dtype == np.dtype(object):
            # object ndarray of str/bytes: pointer + measured payloads
            sample = self._values[:1024]
            payload = sum(len(x) if isinstance(x, (str, bytes)) else 16 for x in sample if x is not None)
            avg = payload / max(len(sample), 1)
            total = int(self._len * (8 + avg + 49))  # ptr + payload + PyObject overhead
        else:
            total = self._values.nbytes
        if self.validity is not None:
            total += self.validity.nbytes
        return total

    def __len__(self) -> int:
        return self._len

    @property
    def null_count(self) -> int:
        return 0 if self.validity is None else int((~self.validity).sum())

    def is_null(self) -> np.ndarray:
        if self.validity is None:
            return np.zeros(self._len, dtype=np.bool_)
        return ~self.validity

    def valid_mask(self) -> np.ndarray:
        if self.validity is None:
            return np.ones(self._len, dtype=np.bool_)
        return self.validity

    def take(self, indices: np.ndarray) -> "Column":
        m = None if self.validity is None else self.validity.take(indices)
        if self._values is None:
            if self.arrow is None:
                pool, codes = self.dict_cache
                return Column.from_codes(pool, codes.take(indices), m)
            import pyarrow.compute as pc

            out = Column(validity=m, arrow=pc.take(self.arrow, indices))
        else:
            out = Column(self.values.take(indices), m)
        return self._with_cache(out, lambda c: c.take(indices))

    def slice(self, start: int, stop: int) -> "Column":
        m = None if self.validity is None else self.validity[start:stop]
        if self._values is None:
            if self.arrow is None:
                pool, codes = self.dict_cache
                return Column.from_codes(pool, codes[start:stop], m)
            out = Column(validity=m, arrow=self.arrow.slice(start, stop - start))
        else:
            out = Column(self.values[start:stop], m)
        return self._with_cache(out, lambda c: c[start:stop])

    def filter(self, mask: np.ndarray) -> "Column":
        m = None if self.validity is None else self.validity[mask]
        if self._values is None:
            if self.arrow is None:
                pool, codes = self.dict_cache
                return Column.from_codes(pool, codes[mask], m)
            import pyarrow.compute as pc

            out = Column(validity=m, arrow=pc.filter(self.arrow, mask))
        else:
            out = Column(self.values[mask], m)
        return self._with_cache(out, lambda c: c[mask])

    def to_pylist(self) -> list:
        if self._values is None and self.arrow is not None and self.validity is None:
            return self.arrow.to_pylist()
        if self.validity is None:
            return self.values.tolist()
        return [v if ok else None for v, ok in zip(self.values.tolist(), self.validity.tolist())]

    @staticmethod
    def from_pylist(data: Sequence[Any], dtype: DataType) -> "Column":
        np_dtype = dtype.numpy_dtype()
        if isinstance(data, np.ndarray):
            # vectorized ingest fast paths: callers handing numpy arrays
            # (bench/engine surfaces) must not pay a per-element loop
            if np_dtype != np.dtype(object) and data.dtype.kind in "biuf":
                return Column(np.ascontiguousarray(data, dtype=np_dtype))
            if np_dtype == data.dtype == np.dtype(object):
                validity = np.asarray(data != None, dtype=np.bool_)  # noqa: E711 — elementwise
                return Column(data, None if validity.all() else validity)
        validity = np.array([x is not None for x in data], dtype=np.bool_)
        if np_dtype == np.dtype(object):
            values = np.empty(len(data), dtype=object)
            for i, x in enumerate(data):
                values[i] = x
        else:
            fill: Any = 0
            values = np.array([fill if x is None else x for x in data], dtype=np_dtype)
        return Column(values, None if validity.all() else validity)

    @staticmethod
    def concat(cols: Sequence["Column"]) -> "Column":
        validity = None
        if not all(c.validity is None for c in cols):
            validity = np.concatenate([c.valid_mask() for c in cols])
        if cols and all(c.is_code_backed for c in cols):
            # code-domain concat: unify the input pools and re-map codes —
            # no string object materializes (ops.dicts; None = domain past
            # the pool limit, fall through to the expanded paths)
            from ..ops.dicts import unify_columns

            out = unify_columns(cols, validity)
            if out is not None:
                return out
        chunks = _arrow_chunks(cols)
        if chunks is not None:
            import pyarrow as pa

            return Column(validity=validity, arrow=pa.concat_arrays(chunks))
        values = np.concatenate([c.values for c in cols])
        return Column(values, validity)

    @staticmethod
    def take_from_parts(parts: Sequence["Column"], plan: PartsTake, out: np.ndarray | None = None) -> tuple["Column", bool]:
        """Column.concat(parts).take(plan.take), cell for cell, and whether
        no one concatenated the parts to make it. True: numpy-valued parts of
        one dtype (one output, `out` where it is given and of that dtype and
        else a fresh one, and, a pick of the plan at a time,
        `out[positions] = part.take(rows)`) and code-backed parts (the pools
        unified, the winners' codes re-mapped only). False: arrow-backed
        parts, which go to pyarrow's take over the chunks (it joins
        variable-width chunks inside the kernel), and any shape that takes
        the concatenation after all (parts backed in different ways or of
        different types, a unified pool past the limit: Column.concat then
        tries the pools again). `plan.take` is not negative: IndexError,
        where Column.take would count from the end."""
        n = len(plan.take)
        validity = None
        if not all(c.validity is None for c in parts):
            validity = _scatter_parts([c.validity for c in parts], plan, np.ones(n, dtype=np.bool_))
        if all(c._values is not None for c in parts) and len({c._values.dtype for c in parts}) == 1:
            dtype = parts[0]._values.dtype
            if out is None or out.dtype != dtype:
                out = np.empty(n, dtype=dtype)
            return Column(_scatter_parts([c._values for c in parts], plan, out), validity), True
        if all(c.is_code_backed for c in parts):
            from ..ops.dicts import remap_codes, unify_column_pools

            got = unify_column_pools(parts)
            if got is not None:
                pool, remaps = got
                codes = np.empty(n, dtype=np.uint32)
                for f, pos, local in plan.picks:
                    codes[pos] = remap_codes(remaps[f], parts[f].dict_cache[1].take(local))
                return Column.from_codes(pool, codes, validity), True
        chunks = _arrow_chunks(parts)
        if chunks is not None:
            import pyarrow as pa
            import pyarrow.compute as pc

            taken = pc.take(pa.chunked_array(chunks), plan.take)
            return Column(validity=validity, arrow=taken.chunk(0) if taken.num_chunks == 1 else taken.combine_chunks()), False
        return Column.concat(parts).take(plan.take), False


def _arrow_chunks(cols: Sequence[Column]) -> list | None:
    """The chunks of arrow-backed columns, in order and under their one type
    (null-typed chunks cast to it); None where a column is not arrow-backed,
    all chunks are null-typed or their types differ."""
    if not cols or not all(c._values is None and c.arrow is not None for c in cols):
        return None
    import pyarrow as pa

    chunks = []
    for c in cols:
        chunks.extend(c.arrow.chunks if isinstance(c.arrow, pa.ChunkedArray) else [c.arrow])
    types = {c.type for c in chunks if not pa.types.is_null(c.type)}
    if len(types) != 1:
        return None
    t = types.pop()
    return [c.cast(t) if pa.types.is_null(c.type) else c for c in chunks]


def _scatter_parts(arrays: Sequence[np.ndarray | None], plan: PartsTake, out: np.ndarray) -> np.ndarray:
    for f, pos, local in plan.picks:
        if arrays[f] is not None:
            out[pos] = arrays[f].take(local)
    return out


class ColumnBatch:
    """A schema-carrying bundle of equal-length Columns."""

    def __init__(self, schema: RowType, columns: Mapping[str, Column] | Sequence[Column]):
        self.schema = schema
        if isinstance(columns, Mapping):
            cols = {name: columns[name] for name in schema.field_names}
        else:
            cols = {f.name: c for f, c in zip(schema.fields, columns)}
        assert len(cols) == len(schema.fields), (list(cols), schema.field_names)
        lengths = {len(c) for c in cols.values()}
        assert len(lengths) <= 1, f"ragged columns: { {n: len(c) for n, c in cols.items()} }"
        self.columns: dict[str, Column] = cols
        self._num_rows = lengths.pop() if lengths else 0

    # ---- construction --------------------------------------------------
    @staticmethod
    def from_pydict(schema: RowType, data: Mapping[str, Sequence[Any]]) -> "ColumnBatch":
        cols = {f.name: Column.from_pylist(data[f.name], f.type) for f in schema.fields}
        return ColumnBatch(schema, cols)

    @staticmethod
    def from_pylist(schema: RowType, rows: Sequence[Sequence[Any]]) -> "ColumnBatch":
        data = {f.name: [r[i] for r in rows] for i, f in enumerate(schema.fields)}
        return ColumnBatch.from_pydict(schema, data)

    @staticmethod
    def empty(schema: RowType) -> "ColumnBatch":
        cols = {
            f.name: Column(np.empty(0, dtype=f.type.numpy_dtype()))
            for f in schema.fields
        }
        return ColumnBatch(schema, cols)

    # ---- accessors -----------------------------------------------------
    @property
    def num_rows(self) -> int:
        return self._num_rows

    def byte_size(self) -> int:
        """Approximate heap bytes across all columns (budgeting currency)."""
        return sum(c.byte_size() for c in self.columns.values())

    def __len__(self) -> int:
        return self._num_rows

    def column(self, name: str) -> Column:
        return self.columns[name]

    def __getitem__(self, name: str) -> Column:
        return self.columns[name]

    # ---- structural ops ------------------------------------------------
    def take(self, indices: np.ndarray) -> "ColumnBatch":
        return ColumnBatch(self.schema, {n: c.take(indices) for n, c in self.columns.items()})

    def slice(self, start: int, stop: int) -> "ColumnBatch":
        return ColumnBatch(self.schema, {n: c.slice(start, stop) for n, c in self.columns.items()})

    def filter(self, mask: np.ndarray) -> "ColumnBatch":
        return ColumnBatch(self.schema, {n: c.filter(mask) for n, c in self.columns.items()})

    def select(self, names: Iterable[str]) -> "ColumnBatch":
        names = list(names)
        return ColumnBatch(self.schema.project(names), {n: self.columns[n] for n in names})

    def with_column(self, field: DataField, col: Column) -> "ColumnBatch":
        fields = list(self.schema.fields) + [field]
        cols = dict(self.columns)
        cols[field.name] = col
        return ColumnBatch(RowType(fields), cols)

    def rename(self, schema: RowType) -> "ColumnBatch":
        """Reinterpret under a same-arity schema (positional)."""
        assert len(schema) == len(self.schema)
        cols = {
            nf.name: self.columns[of.name]
            for of, nf in zip(self.schema.fields, schema.fields)
        }
        return ColumnBatch(schema, cols)

    # ---- conversion ----------------------------------------------------
    def to_pydict(self) -> dict[str, list]:
        return {n: c.to_pylist() for n, c in self.columns.items()}

    def to_pylist(self) -> list[tuple]:
        cols = [self.columns[f.name].to_pylist() for f in self.schema.fields]
        return list(zip(*cols)) if cols else []

    def to_arrow(self):
        import pyarrow as pa

        from ..types import TypeRoot

        arrays = []
        for f in self.schema.fields:
            c = self.columns[f.name]
            if c._values is None and c.arrow is None:
                # code-backed: hand arrow the dictionary form directly —
                # one int32 cast, zero string materialization (parquet
                # writes it as a dictionary-encoded column)
                pool, codes = c.dict_cache
                if len(pool) == 0:  # all-null column: the expanded path's typed nulls
                    arrays.append(pa.nulls(len(c), _pa_nested_type(f.type)))
                    continue
                mask = None if c.validity is None else ~c.validity
                indices = pa.array(
                    np.minimum(codes, max(len(pool) - 1, 0)).astype(np.int32), mask=mask
                )
                arrays.append(pa.DictionaryArray.from_arrays(indices, pa.array(pool, from_pandas=True)))
                continue
            if c._values is None:
                arrays.append(c.arrow)  # zero-conversion passthrough
                continue
            mask = None if c.validity is None else ~c.validity
            if f.type.root in (TypeRoot.ARRAY, TypeRoot.MAP, TypeRoot.ROW):
                # nested columns need the declared type: inference cannot see
                # struct shapes through object ndarrays. The null-free fast
                # path hands the object vector over in one C pass; nulls take
                # one vectorized mask-assign on a copy — no per-row loop
                if mask is None:
                    vals = list(c.values)
                else:
                    masked = c.values.copy()
                    masked[mask] = None
                    vals = list(masked)
                arrays.append(pa.array(vals, type=_pa_nested_type(f.type)))
            else:
                arr = pa.array(c.values, from_pandas=True, mask=mask)
                if pa.types.is_null(arr.type) and f.type.root in _STRING_ROOTS:
                    # all null: inference finds no type, and ORC writes no null-typed column
                    arr = pa.nulls(len(c), _pa_nested_type(f.type))
                arrays.append(arr)
        return pa.table(dict(zip(self.schema.field_names, arrays)))

    @staticmethod
    def row_type_from_arrow(arrow_schema) -> RowType:
        """Infer a RowType from a pyarrow schema (migration entry point)."""
        import pyarrow as pa

        from ..types import (
            BIGINT,
            BOOLEAN,
            BYTES,
            DATE,
            DOUBLE,
            FLOAT,
            INT,
            SMALLINT,
            STRING,
            TIMESTAMP,
            TINYINT,
            DataField,
        )

        def conv(t):
            if pa.types.is_boolean(t):
                return BOOLEAN()
            if pa.types.is_int8(t):
                return TINYINT()
            if pa.types.is_int16(t):
                return SMALLINT()
            if pa.types.is_int32(t):
                return INT()
            if pa.types.is_integer(t):
                return BIGINT()
            if pa.types.is_float32(t):
                return FLOAT()
            if pa.types.is_floating(t):
                return DOUBLE()
            if pa.types.is_date(t):
                return DATE()
            if pa.types.is_timestamp(t):
                return TIMESTAMP()
            if pa.types.is_binary(t) or pa.types.is_large_binary(t):
                return BYTES()
            if pa.types.is_decimal(t) and t.precision <= 18:
                from ..types import DECIMAL

                return DECIMAL(t.precision, t.scale)
            return STRING()

        return RowType(
            tuple(DataField(i, f.name, conv(f.type)) for i, f in enumerate(arrow_schema))
        )

    @staticmethod
    def from_arrow(table, schema: RowType) -> "ColumnBatch":
        cols: dict[str, Column] = {}
        for f in schema.fields:
            arr = table.column(f.name).combine_chunks()
            cols[f.name] = _arrow_to_column(arr, f.type)
        return ColumnBatch(schema, cols)

    def __repr__(self) -> str:  # pragma: no cover
        return f"ColumnBatch(rows={self.num_rows}, fields={self.schema.field_names})"


def _restore_nested(x, dtype: DataType):
    """Recursively restore dict shape for maps at ANY nesting depth (arrow
    reads maps back as [(k, v), ...] pair lists)."""
    if x is None:
        return None
    root = dtype.root
    if root == TypeRoot.MAP:
        return {k: _restore_nested(v, dtype.value) for k, v in x}
    if root == TypeRoot.ARRAY:
        return [_restore_nested(e, dtype.element) for e in x]
    if root == TypeRoot.ROW:
        return {f.name: _restore_nested(x.get(f.name), f.type) for f in dtype.fields}
    return x


def _pa_nested_type(dtype: DataType):
    """DataType -> pyarrow type for nested (array/map/row) columns."""
    import pyarrow as pa

    from ..types import TypeRoot

    root = dtype.root
    if root == TypeRoot.ARRAY:
        return pa.list_(_pa_nested_type(dtype.element))
    if root == TypeRoot.MAP:
        return pa.map_(_pa_nested_type(dtype.key), _pa_nested_type(dtype.value))
    if root == TypeRoot.ROW:
        return pa.struct([(f.name, _pa_nested_type(f.type)) for f in dtype.fields])
    np_dtype = dtype.numpy_dtype()
    if np_dtype == np.dtype(object):
        return pa.binary() if root in (TypeRoot.BINARY, TypeRoot.VARBINARY) else pa.string()
    return pa.from_numpy_dtype(np_dtype)


def _arrow_to_column(arr, dtype: DataType) -> Column:
    import pyarrow as pa
    import pyarrow.compute as pc

    validity = None
    if arr.null_count:
        validity = np.asarray(pc.is_valid(arr))
    np_dtype = dtype.numpy_dtype()
    if (
        np_dtype == np.dtype(object)
        and pa.types.is_dictionary(arr.type)
        and not pa.types.is_nested(arr.type.value_type)
        and arr.dictionary.null_count == 0
    ):
        # arrow decoded the chunk dictionary-encoded (read_dictionary under
        # merge.dict-domain): populate the code domain in one C pass —
        # indices + dictionary straight off the buffers, never a string
        # object per row (the arrow twin of decode/pages.chunk_codes)
        from ..metrics import dict_metrics
        from ..ops.dicts import remap_codes, resolve_pool_limit, sort_dictionary

        if len(arr.dictionary) <= resolve_pool_limit(None):
            indices = arr.indices
            if indices.null_count:
                indices = pc.fill_null(indices, 0)
            codes = indices.to_numpy(zero_copy_only=False).astype(np.uint32, copy=False)
            dictionary = arr.dictionary.to_numpy(zero_copy_only=False)
            if dictionary.dtype != np.dtype(object):
                dictionary = dictionary.astype(object)
            pool, remap = sort_dictionary(dictionary)
            dict_metrics().counter("rows_code_domain").inc(len(codes))
            return Column.from_codes(pool, remap_codes(remap, codes), validity)
        dict_metrics().counter("fallback_expanded").inc(len(arr))
    if (
        np_dtype != np.dtype(object)
        and np_dtype.kind in "iu"
        and pa.types.is_dictionary(arr.type)
        and not pa.types.is_nested(arr.type.value_type)
        and arr.dictionary.null_count == 0
    ):
        # fixed-width dictionary (int/date/timestamp — ISSUE 12): same one-
        # C-pass code-domain population as the string branch, with the pool
        # kept in the column's native numpy dtype
        from ..metrics import dict_metrics
        from ..ops.dicts import remap_codes, resolve_pool_limit, sort_dictionary

        if len(arr.dictionary) <= resolve_pool_limit(None):
            d = arr.dictionary
            if pa.types.is_timestamp(d.type):
                d = d.cast(pa.int64())
            elif pa.types.is_date32(d.type):
                d = d.cast(pa.int32())
            dnp = d.to_numpy(zero_copy_only=False)
            if dnp.dtype != np_dtype and dnp.dtype.kind in "iu":
                dnp = dnp.astype(np_dtype)
            if dnp.dtype == np_dtype:
                indices = arr.indices
                if indices.null_count:
                    indices = pc.fill_null(indices, 0)
                codes = indices.to_numpy(zero_copy_only=False).astype(np.uint32, copy=False)
                pool, remap = sort_dictionary(dnp)
                dict_metrics().counter("rows_code_domain").inc(len(codes))
                return Column.from_codes(pool, remap_codes(remap, codes), validity)
        dict_metrics().counter("fallback_expanded").inc(len(arr))
    if pa.types.is_dictionary(arr.type):
        # dictionary shape the code domain can't carry (nested values,
        # null dictionary entries, float/decimal dictionary): decode to the
        # plain type and take the ordinary paths below
        arr = arr.cast(arr.type.value_type)
    if np_dtype == np.dtype(object):
        if pa.types.is_nested(arr.type):
            # nested (list/map/struct) values must stay python lists/dicts —
            # to_numpy would hand back ndarrays whose equality semantics break
            values = np.empty(len(arr), dtype=object)
            for i, x in enumerate(arr.to_pylist()):
                values[i] = _restore_nested(x, dtype)
        else:
            # keep the arrow backing: structural ops stay in C++ and the
            # object ndarray materializes only if python-level access happens
            return Column(validity=validity, arrow=arr)
    else:
        if arr.null_count:
            arr = arr.fill_null(_zero_value(dtype))
        if pa.types.is_timestamp(arr.type):
            arr = arr.cast(pa.int64())
        elif pa.types.is_date32(arr.type):
            arr = arr.cast(pa.int32())
        elif pa.types.is_decimal(arr.type):
            # exact unscaled int64: stay in decimal space (no float detour)
            scale = arr.type.scale
            widened = arr.cast(pa.decimal256(38, scale))
            arr = pc.multiply(widened, pa.scalar(10**scale, pa.decimal256(20, 0))).cast(pa.int64())
        values = arr.to_numpy(zero_copy_only=False).astype(np_dtype, copy=False)
    return Column(values, validity)


def _zero_value(dtype: DataType):
    if dtype.root == TypeRoot.BOOLEAN:
        return False
    return 0


def concat_batches(batches: Sequence[ColumnBatch]) -> ColumnBatch:
    if not batches:
        raise ValueError("no batches")
    non_empty = [b for b in batches if b.num_rows]
    batches = non_empty or [batches[0]]
    if len(batches) == 1:
        return batches[0]
    schema = batches[0].schema
    cols = {
        n: Column.concat([b.columns[n] for b in batches]) for n in schema.field_names
    }
    return ColumnBatch(schema, cols)


class BatchSink:
    """concat_batches of the batches appended, cell for cell and backing for
    backing, built once: the result of a read of several splits without the
    whole-table copy at its end.

    A column of a fixed-width type has one array of `capacity` rows, made
    before the first batch arrives (np.empty: a page nobody writes to is
    never faulted, and the result's columns are the views [:rows]). It fills
    through two entries:

      * reserve(rows): the arrays' next `rows` rows, name -> view, for a
        producer that can write its rows where they belong (the keys-only
        pipeline's gather, core/read.py). The cursor stays.
      * append(batch): the next rows of the result. A column whose values
        ARE the view reserved for it is in place already; any other
        numpy-valued column of the array's dtype is copied in at the cursor.

    A column that cannot be sized beforehand (arrow-backed, code-backed,
    object-valued), or whose batches disagree with its array (another backing
    or dtype), keeps its parts for one Column.concat in result(): what
    concat_batches does to every column; with `capacity` None (the caller
    has no bound of the order of its result) that is every column, and no
    array is made. A validity array is made when the first batch brings one.
    `placed` counts the cells (rows x columns) written through reserve,
    `joined` those that append or result() copied.

    reserve and append belong to one thread, in batch order; a producer may
    fill the reserved views from any thread before its batch is appended."""

    def __init__(self, schema: RowType, capacity: int | None):
        self.schema = schema
        self.capacity = capacity
        self.rows = 0
        dtypes = {} if capacity is None else {f.name: f.type.numpy_dtype() for f in schema.fields}
        self._arrays = {n: np.empty(capacity, dtype=d) for n, d in dtypes.items() if d != np.dtype(object)}
        self._validity: dict[str, np.ndarray] = {}
        self._parts: dict[str, list[Column]] = {n: [] for n in schema.field_names if n not in self._arrays}
        self._placed = dict.fromkeys(self._arrays, 0)
        self._reserved: dict[str, np.ndarray] = {}

    @property
    def placed(self) -> int:
        return sum(self._placed.values())

    @property
    def joined(self) -> int:
        return self.rows * len(self.schema.fields) - self.placed

    def reserve(self, rows: int) -> dict[str, np.ndarray]:
        self._reserved = {n: a[self.rows : self.rows + rows] for n, a in self._arrays.items()}
        return self._reserved

    def append(self, batch: ColumnBatch) -> None:
        start, stop = self.rows, self.rows + batch.num_rows
        if self.capacity is not None and stop > self.capacity:
            raise ValueError(f"row {stop} of a result sized for {self.capacity}")
        if start == stop:  # concat_batches drops an empty batch too, whatever backs its columns
            return
        for name in self.schema.field_names:
            col = batch.columns[name]
            array = self._arrays.get(name)
            if array is not None and (col._values is None or col._values.dtype != array.dtype):
                # what is in the array becomes the column's first part
                self._parts[name] = [self._view(name, start)] if start else []
                del self._arrays[name], self._placed[name]
                array = None
            if array is None:
                self._parts[name].append(col)
                continue
            if col._values is self._reserved.get(name):
                self._placed[name] += stop - start
            else:
                array[start:stop] = col._values
            valid = self._validity.get(name)
            if valid is None and col.validity is not None:
                valid = self._validity[name] = np.empty(self.capacity, dtype=np.bool_)
                valid[:start] = True
            if valid is not None:
                valid[start:stop] = True if col.validity is None else col.validity
        self.rows = stop
        self._reserved = {}

    def _view(self, name: str, rows: int) -> Column:
        valid = self._validity.get(name)
        return Column(self._arrays[name][:rows], None if valid is None else valid[:rows])

    def result(self, map_fn=map) -> ColumnBatch:
        """`map_fn(fn, names)` joins the columns kept as parts (the read path
        gives its pool's map: a column a task)."""

        def join(name: str) -> Column:
            parts = self._parts[name]
            if not parts:
                return Column(np.empty(0, dtype=self.schema.field(name).type.numpy_dtype()))
            return parts[0] if len(parts) == 1 else Column.concat(parts)

        joined = dict(zip(self._parts, map_fn(join, list(self._parts))))
        return ColumnBatch(self.schema, {n: joined[n] if n in joined else self._view(n, self.rows) for n in self.schema.field_names})
