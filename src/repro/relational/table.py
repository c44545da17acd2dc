"""Columnar tables backed by JAX arrays.

The relational substrate of the Cobra reproduction. Tables are columnar
(dict of 1-D ``jnp`` arrays); all bulk compute (filters, gathers, joins,
aggregations) runs through ``jax.numpy`` so the data path is real JAX
compute. Index machinery that is inherently dynamic-shape (sort/unique/
searchsorted on concrete row counts) uses numpy on host — this mirrors a
database runtime, where the executor is not a compiled graph.

Wire sizes are modeled separately from storage dtype: a ``varchar(100)``
column is stored as an int32 surrogate key but declares 100 wire bytes,
so that the simulated network-transfer costs match the paper's TPC-DS
row sizing (Sec. VIII).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.transfer import to_device, to_host

__all__ = ["Field", "Schema", "Table"]


def _storage_dtype(dtype: str) -> np.dtype:
    """Storage dtype; 64-bit narrows to 32-bit unless jax_enable_x64 is set.

    Wire sizes (cost model) always honor the declared Field dtype/wire_bytes;
    only in-memory storage narrows.
    """
    dt = np.dtype(dtype)
    if dt.itemsize == 8 and not jax.config.jax_enable_x64:
        return np.dtype("int32") if dt.kind in "iu" else np.dtype("float32")
    return dt


@dataclasses.dataclass(frozen=True)
class Field:
    """One column: storage dtype + simulated wire width in bytes."""

    name: str
    dtype: str = "int32"  # numpy dtype string: int32/int64/float32/float64
    wire_bytes: Optional[int] = None  # defaults to dtype itemsize

    @property
    def itemsize(self) -> int:
        return int(np.dtype(self.dtype).itemsize)

    @property
    def bytes_on_wire(self) -> int:
        return self.wire_bytes if self.wire_bytes is not None else self.itemsize


@dataclasses.dataclass(frozen=True)
class Schema:
    fields: Tuple[Field, ...]

    def __post_init__(self):
        names = [f.name for f in self.fields]
        if len(names) != len(set(names)):
            raise ValueError(f"duplicate column names in schema: {names}")

    @staticmethod
    def of(*fields: Field) -> "Schema":
        return Schema(tuple(fields))

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(f.name for f in self.fields)

    def field(self, name: str) -> Field:
        for f in self.fields:
            if f.name == name:
                return f
        raise KeyError(f"no column {name!r}; have {self.names}")

    def has(self, name: str) -> bool:
        return any(f.name == name for f in self.fields)

    @property
    def row_bytes(self) -> int:
        """Simulated size of one row on the wire."""
        return sum(f.bytes_on_wire for f in self.fields)

    def subset(self, names: Sequence[str]) -> "Schema":
        return Schema(tuple(self.field(n) for n in names))

    def rename_prefixed(self, prefix: str) -> "Schema":
        return Schema(tuple(dataclasses.replace(f, name=prefix + f.name) for f in self.fields))

    def concat(self, other: "Schema") -> "Schema":
        return Schema(self.fields + other.fields)


class Table:
    """An immutable columnar table. Columns are 1-D jnp arrays of equal length."""

    def __init__(self, name: str, schema: Schema, columns: Mapping[str, jnp.ndarray]):
        self.name = name
        self.schema = schema
        cols: Dict[str, jnp.ndarray] = {}
        n = None
        for f in schema.fields:
            if f.name not in columns:
                raise KeyError(f"missing column {f.name!r} for table {name!r}")
            arr = to_device(columns[f.name], _storage_dtype(f.dtype),
                            "table.from_columns")
            if arr.ndim != 1:
                raise ValueError(f"column {f.name!r} must be 1-D, got shape {arr.shape}")
            if n is None:
                n = int(arr.shape[0])
            elif int(arr.shape[0]) != n:
                raise ValueError(
                    f"column {f.name!r} has {arr.shape[0]} rows, expected {n}"
                )
            cols[f.name] = arr
        self.columns = cols
        self._nrows = 0 if n is None else n

    # ---------------------------------------------------------------- basics
    @property
    def nrows(self) -> int:
        return self._nrows

    @property
    def row_bytes(self) -> int:
        return self.schema.row_bytes

    @property
    def wire_bytes(self) -> int:
        return self.nrows * self.row_bytes

    def column(self, name: str) -> jnp.ndarray:
        return self.columns[name]

    def __repr__(self) -> str:
        return f"Table({self.name!r}, rows={self.nrows}, cols={list(self.schema.names)})"

    # ----------------------------------------------------------- constructors
    @staticmethod
    def from_columns(name: str, schema: Schema, **columns) -> "Table":
        return Table(name, schema, columns)

    @staticmethod
    def from_rows(name: str, schema: Schema, rows: Iterable[Mapping[str, object]]) -> "Table":
        rows = list(rows)
        cols = {
            f.name: np.asarray([r[f.name] for r in rows], dtype=_storage_dtype(f.dtype))
            if rows
            else np.asarray([], dtype=_storage_dtype(f.dtype))
            for f in schema.fields
        }
        return Table(name, schema, cols)

    def empty_like(self) -> "Table":
        return Table(
            self.name,
            self.schema,
            {f.name: np.asarray([], dtype=_storage_dtype(f.dtype)) for f in self.schema.fields},
        )

    # ------------------------------------------------------------- row access
    def row(self, i: int) -> Dict[str, object]:
        return {n: to_host(self.columns[n][i], "table.row").item()
                for n in self.schema.names}

    def to_rows(self) -> List[Dict[str, object]]:
        host = {n: to_host(self.columns[n], "table.to_rows")
                for n in self.schema.names}
        return [{n: host[n][i].item() for n in self.schema.names} for i in range(self.nrows)]

    # ------------------------------------------------------------- transforms
    def take(self, idx) -> "Table":
        idx = to_device(idx, None, "table.take")
        return Table(self.name, self.schema, {n: jnp.take(c, idx, axis=0) for n, c in self.columns.items()})

    def filter_mask(self, mask) -> "Table":
        keep = np.flatnonzero(to_host(mask, "table.filter_mask"))
        return self.take(keep)

    def head(self, k: int) -> "Table":
        return self.take(np.arange(min(k, self.nrows)))

    def select_columns(self, names: Sequence[str]) -> "Table":
        return Table(self.name, self.schema.subset(names), {n: self.columns[n] for n in names})

    def rename(self, mapping: Mapping[str, str]) -> "Table":
        fields = tuple(
            dataclasses.replace(f, name=mapping.get(f.name, f.name)) for f in self.schema.fields
        )
        cols = {mapping.get(n, n): c for n, c in self.columns.items()}
        return Table(self.name, Schema(fields), cols)

    def with_column(self, field: Field, values) -> "Table":
        values = to_device(values, _storage_dtype(field.dtype),
                           "table.with_column")
        if self.schema.has(field.name):
            fields = tuple(field if f.name == field.name else f for f in self.schema.fields)
        else:
            fields = self.schema.fields + (field,)
        cols = dict(self.columns)
        cols[field.name] = values
        return Table(self.name, Schema(fields), cols)

    def sort_by(self, keys: Sequence[str], descending: bool = False) -> "Table":
        if self.nrows == 0:
            return self
        arrs = [to_host(self.columns[k], "table.sort_by")
                for k in reversed(list(keys))]
        order = np.lexsort(arrs)
        if descending:
            order = order[::-1]
        return self.take(order)

    def concat_rows(self, other: "Table") -> "Table":
        if self.schema.names != other.schema.names:
            raise ValueError("schema mismatch in concat")
        cols = {
            n: jnp.concatenate([self.columns[n], other.columns[n]]) for n in self.schema.names
        }
        return Table(self.name, self.schema, cols)

    # ------------------------------------------------------------- comparison
    def canonical_key(self) -> np.ndarray:
        """Row-set canonical form (sorted rows over sorted column names)."""
        names = sorted(self.schema.names)
        mat = np.stack([np.asarray(self.columns[n], dtype=np.float64) for n in names], axis=1)
        if mat.shape[0] > 1:
            order = np.lexsort(tuple(mat[:, j] for j in reversed(range(mat.shape[1]))))
            mat = mat[order]
        return mat

    def same_rows(self, other: "Table", ordered: bool = False, atol: float = 1e-6) -> bool:
        """Semantic equality: same multiset (or sequence) of rows."""
        if sorted(self.schema.names) != sorted(other.schema.names):
            return False
        if self.nrows != other.nrows:
            return False
        if self.nrows == 0:
            return True
        if ordered:
            names = sorted(self.schema.names)
            a = np.stack([np.asarray(self.columns[n], np.float64) for n in names], 1)
            b = np.stack([np.asarray(other.columns[n], np.float64) for n in names], 1)
            return bool(np.allclose(a, b, atol=atol))
        return bool(np.allclose(self.canonical_key(), other.canonical_key(), atol=atol))
