"""The dataframe value container: DF = (A_mn, R_m, C_n, D_n)  (paper §3.2).

``Frame`` is a *single-partition* dataframe instance: the unit that Pallas
kernels and per-shard physical operators execute on.  Distribution happens one
level up (``partition.PartitionedFrame`` / shard_map in ``physical.py``).

Representation (DESIGN.md §3 — hardware adaptation):
  * one 1-D device array per column in its domain's storage dtype,
  * optional validity mask per column (None = all valid),
  * host-side code table per coded (Σ*/category) column,
  * row labels R_m and column labels C_n as ``labels.Labels`` metadata,
  * schema D_n as a tuple of ``Domain`` (UNSPECIFIED entries are induced on
    demand by S(·) — ``induce()``),
  * optional ``row_domains``: the pre-TRANSPOSE schema, letting a second
    TRANSPOSE recover the original D_n (paper §3.3: "the schema induction
    function can always recover the original D_n after two transposes").
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .dtypes import (
    Domain,
    ParsedColumn,
    common_storage,
    induce_schema,
    parse_column,
    storage_dtype,
)
from .labels import CodedLabels, Labels, RangeLabels, labels_from_values
from .transfer import to_device, to_host

__all__ = ["Column", "Frame"]


@dataclasses.dataclass(frozen=True)
class Column:
    """One column of A_mn with its domain, validity mask, and code table."""

    data: jnp.ndarray          # (m,) storage-dtype device array
    domain: Domain
    mask: jnp.ndarray | None = None   # (m,) bool, True = valid; None = all valid
    dictionary: tuple | None = None   # host code table when domain.is_coded

    def __len__(self) -> int:
        return int(self.data.shape[0])

    # ---- host materialization -------------------------------------------
    def to_pylist(self) -> list:
        data = to_host(self.data)
        mask = to_host(self.mask) if self.mask is not None else None
        out: list = []
        for i in range(data.shape[0]):
            if mask is not None and not mask[i]:
                out.append(None)
            elif self.domain.is_coded:
                code = int(data[i])
                out.append(self.dictionary[code] if 0 <= code < len(self.dictionary) else None)
            elif self.domain is Domain.BOOL:
                out.append(bool(data[i]))
            elif self.domain is Domain.INT:
                out.append(int(data[i]))
            else:
                out.append(float(data[i]))
        return out

    def valid_mask(self) -> jnp.ndarray | np.ndarray:
        if self.mask is not None:
            return self.mask
        # host ones: consumers promote lazily when a device op needs them
        return np.ones(self.data.shape[0], dtype=np.bool_)

    def value_at(self, i: int):
        """Decode a single position from the column's host copy (a device
        column's is fetched once and cached, ``transfer.to_host``), so a
        resident block costs no device program per position."""
        if self.mask is not None and not bool(to_host(self.mask)[i]):
            return None
        v = to_host(self.data)[i]
        if self.domain.is_coded:
            code = int(v)
            return self.dictionary[code] if 0 <= code < len(self.dictionary) else None
        if self.domain is Domain.BOOL:
            return bool(v)
        if self.domain is Domain.INT:
            return int(v)
        return float(v)

    def take(self, idx) -> "Column":
        """Row gather as a host numpy view that re-enters the device lazily,
        on every backend.  Take lengths are data-dependent, and on a device
        each new length compiles a new XLA program: a sample-sort's per-block
        gathers and concats build one program per piece, and a 1M-row sort
        on a TPU v5e did not finish in nine minutes.  A device column, such
        as a resident source block (``Frame.split_rows``), has its host copy
        fetched on the first take and cached on the array: every later take
        of that block, in any statement, reads the cached copy and moves
        nothing (counted once, ``transfer.to_host``)."""
        idx_np = to_host(idx)
        return Column(
            to_host(self.data)[idx_np], self.domain,
            None if self.mask is None else to_host(self.mask)[idx_np],
            self.dictionary)

    def filter(self, keep: jnp.ndarray) -> "Column":
        return self.take(np.nonzero(to_host(keep))[0])

    def astype_storage(self, target: Domain) -> jnp.ndarray:
        """Numeric view of this column in ``target``'s storage dtype.

        Coded columns decode to their *codes* when the target is coded; when
        the target is numeric the codes are meaningless and we surface NaN —
        the same failure mode pandas produces for numeric ops over objects.
        """
        if target.is_coded:
            return self.data.astype(np.int32)
        return self.data.astype(storage_dtype(target))


def _parsed_to_column(p: ParsedColumn) -> Column:
    return Column(p.data, p.domain, p.mask, p.dictionary)


class Frame:
    """A single-partition dataframe (A_mn, R_m, C_n, D_n)."""

    def __init__(
        self,
        columns: Sequence[Column],
        row_labels: Labels,
        col_labels: Labels,
        row_domains: tuple[Domain, ...] | None = None,
    ):
        self.columns = list(columns)
        self.row_labels = row_labels
        self.col_labels = col_labels
        # Pre-transpose schema carried along for recovery after a second
        # TRANSPOSE (paper §3.3 / §5 "types maintained at both row and column
        # level ... type inference faster after a transpose").
        self.row_domains = row_domains
        m = len(row_labels)
        for c in self.columns:
            assert len(c) == m, f"column length {len(c)} != nrows {m}"
        assert len(col_labels) == len(self.columns)

    # ------------------------------------------------------------------
    # constructors
    # ------------------------------------------------------------------
    @staticmethod
    def from_pydict(
        data: dict[str, Sequence[Any]],
        row_labels: Sequence[Any] | None = None,
        domains: Sequence[Domain] | None = None,
    ) -> "Frame":
        names = list(data.keys())
        cols = []
        for j, name in enumerate(names):
            dom = domains[j] if domains is not None else None
            cols.append(_parsed_to_column(parse_column(list(data[name]), dom)))
        m = len(cols[0]) if cols else 0
        rl = labels_from_values(list(row_labels)) if row_labels is not None else RangeLabels(m)
        return Frame(cols, rl, labels_from_values(names))

    @staticmethod
    def from_matrix(
        values: jnp.ndarray,
        domain: Domain = Domain.FLOAT,
        row_labels: Labels | None = None,
        col_labels: Labels | None = None,
    ) -> "Frame":
        """Homogeneous ("matrix dataframe", paper §3.2) constructor.

        Wide-frame fast path: one host materialization + numpy column views
        (per-column device slices would cost O(n) dispatches)."""
        m, n = values.shape
        host = to_host(values).astype(storage_dtype(domain), copy=False)
        cols = [Column(host[:, j], domain) for j in range(n)]
        return Frame(
            cols,
            row_labels if row_labels is not None else RangeLabels(m),
            col_labels if col_labels is not None else RangeLabels(n),
        )

    # ------------------------------------------------------------------
    # basic properties
    # ------------------------------------------------------------------
    @property
    def nrows(self) -> int:
        return len(self.row_labels)

    @property
    def ncols(self) -> int:
        return len(self.columns)

    @property
    def shape(self) -> tuple[int, int]:
        return (self.nrows, self.ncols)

    @property
    def schema(self) -> tuple[Domain, ...]:
        return tuple(c.domain for c in self.columns)

    def induce(self) -> "Frame":
        """Apply S(·) to every UNSPECIFIED column (paper §3.2).

        If a pre-transpose row schema was recorded and matches the width,
        recover it directly without scanning values.
        """
        if all(c.domain is not Domain.UNSPECIFIED for c in self.columns):
            return self
        cols = []
        for c in self.columns:
            if c.domain is not Domain.UNSPECIFIED:
                cols.append(c)
                continue
            vals = c.to_pylist()
            cols.append(_parsed_to_column(parse_column(vals, induce_schema(vals))))
        return Frame(cols, self.row_labels, self.col_labels, self.row_domains)

    def is_matrix(self) -> bool:
        """Matrix dataframe (§3.2): every column in a numeric field domain.

        The paper's strict notion is a single shared domain; we accept mixed
        int/float/bool since they embed in **float** (the coercion linear
        algebra applies anyway).  Σ*-typed columns disqualify — opaque strings
        "do not satisfy the properties of a field".
        """
        f = self.induce()
        return all(d.is_numeric for d in f.schema)

    # ------------------------------------------------------------------
    # matrix coercion (for TRANSPOSE / linear-algebra ops)
    # ------------------------------------------------------------------
    def as_matrix(self, target: Domain | None = None) -> tuple[jnp.ndarray, Domain]:
        # explicit target ⇒ no schema induction needed (storage casting only);
        # induction of 10⁵⁺-column UNSPECIFIED frames is O(values) Python.
        f = self if target is not None else self.induce()
        tgt = target or common_storage(f.schema)
        if tgt is Domain.UNSPECIFIED:
            tgt = Domain.FLOAT
        if not f.ncols:
            return jnp.zeros((f.nrows, 0), storage_dtype(tgt)), tgt
        # stack on host (O(1) per column, no per-column device dispatch —
        # matters for post-transpose frames with 10⁵⁺ columns)
        mat_np = np.stack([to_host(c.astype_storage(tgt)) for c in f.columns],
                          axis=1)
        return to_device(mat_np), tgt

    # ------------------------------------------------------------------
    # row/column selection
    # ------------------------------------------------------------------
    def take_rows(self, idx) -> "Frame":
        idx_np = to_host(idx)
        return self._with_rows([c.take(idx_np) for c in self.columns], idx_np)

    def _with_rows(self, columns: list[Column], idx_np: np.ndarray) -> "Frame":
        """``columns`` (rows ``idx_np`` of this frame's) with the row labels
        and row schema of those rows."""
        rd = None
        if self.row_domains is not None and len(self.row_domains) == self.nrows:
            rd = tuple(self.row_domains[int(i)] for i in idx_np)
        return Frame(columns, self.row_labels.take(idx_np), self.col_labels, rd)

    def split_rows(self, sizes: Sequence[int]) -> list["Frame"]:
        """The frame cut into consecutive row blocks of ``sizes`` rows.

        A device array (a column's data or mask) stays on the device: it is
        cut by one program per shape, dtype and ``sizes``, never one slice
        per block (an eager slice compiles once per offset), and a single
        block keeps the array itself.  So the blocks of a registered device
        table are resident, and a statement sends none of them again.  A
        host array is cut as ``take_rows`` cuts it.  The placement follows
        the registered column; the values, and so every result, are the
        same either way."""
        sizes = tuple(int(n) for n in sizes)
        edges = np.cumsum((0,) + sizes)
        rows = [np.arange(r0, r1) for r0, r1 in zip(edges[:-1], edges[1:])]

        def pieces(a) -> list:
            if a is None:
                return [None] * len(rows)
            if isinstance(a, jax.Array):
                return [a] if len(rows) == 1 else _split_device(a, sizes)
            h = to_host(a)
            return [h[idx] for idx in rows]

        cols = [(c, pieces(c.data), pieces(c.mask)) for c in self.columns]
        return [self._with_rows([Column(data[b], c.domain, mask[b], c.dictionary)
                                 for c, data, mask in cols], idx)
                for b, idx in enumerate(rows)]

    def filter_rows(self, keep: np.ndarray) -> "Frame":
        idx = np.nonzero(to_host(keep))[0]
        return self.take_rows(idx)

    def take_cols(self, idx: Sequence[int]) -> "Frame":
        # row_domains is a per-ROW vector (the pre-transpose schema): column
        # selection leaves it intact.  Indexing it by column positions here
        # used to truncate it silently (ncols ≤ nrows) or crash with an
        # IndexError (any column index ≥ nrows — e.g. column-repartitioning a
        # wider-than-tall post-transpose frame).
        idx = list(idx)
        return Frame(
            [self.columns[j] for j in idx],
            self.row_labels,
            self.col_labels.take(np.asarray(idx, dtype=np.int64)),
            self.row_domains,
        )

    def col(self, name: Any) -> Column:
        return self.columns[self.col_labels.position_of(name)]

    def head(self, k: int) -> "Frame":
        return self.take_rows(np.arange(min(k, self.nrows)))

    def tail(self, k: int) -> "Frame":
        k = min(k, self.nrows)
        return self.take_rows(np.arange(self.nrows - k, self.nrows))

    # ------------------------------------------------------------------
    # concatenation (UNION building block — order preserved, paper Table 1)
    # ------------------------------------------------------------------
    def concat_rows(self, other: "Frame") -> "Frame":
        assert self.ncols == other.ncols, "UNION requires equal arity"
        cols = []
        for a, b in zip(self.columns, other.columns):
            a, b = _unify_pair(a, b)
            mask = None
            if a.mask is not None or b.mask is not None:
                mask = _concat_arrays(a.valid_mask(), b.valid_mask())
            cols.append(Column(_concat_arrays(a.data, b.data), a.domain, mask, a.dictionary))
        rd = None
        if (self.row_domains is not None and other.row_domains is not None
                and len(self.row_domains) == self.nrows
                and len(other.row_domains) == other.nrows):
            rd = self.row_domains + other.row_domains
        return Frame(cols, self.row_labels.concat(other.row_labels), self.col_labels, rd)

    def concat_cols(self, other: "Frame") -> "Frame":
        assert self.nrows == other.nrows
        return Frame(
            self.columns + other.columns,
            self.row_labels,
            self.col_labels.concat(other.col_labels),
        )

    # ------------------------------------------------------------------
    # point access/update (ordered point updates, paper §2 C1)
    # ------------------------------------------------------------------
    def iloc_get(self, r: int, c: int) -> Any:
        return self.columns[c].to_pylist()[r]

    def iloc_set(self, r: int, c: int, value: Any) -> "Frame":
        col = self.columns[c]
        if col.domain.is_coded:
            table = list(col.dictionary or ())
            key = str(value)
            if key not in table:
                table.append(key)
            code = table.index(key)
            data = to_device(col.data).at[r].set(np.int32(code))
            new = Column(data, col.domain, _set_valid(col, r), tuple(table))
        else:
            data = to_device(col.data).at[r].set(
                np.asarray(value, dtype=col.data.dtype))
            new = Column(data, col.domain, _set_valid(col, r), None)
        cols = list(self.columns)
        cols[c] = new
        return Frame(cols, self.row_labels, self.col_labels, self.row_domains)

    # ------------------------------------------------------------------
    # host views (display / testing)
    # ------------------------------------------------------------------
    def to_pydict(self) -> dict:
        return {
            name: col.to_pylist()
            for name, col in zip(self.col_labels.to_list(), self.columns)
        }

    def to_records(self) -> list[tuple]:
        cols = [c.to_pylist() for c in self.columns]
        return [tuple(col[i] for col in cols) for i in range(self.nrows)]

    def __repr__(self) -> str:
        names = self.col_labels.to_list()
        doms = [d.value for d in self.schema]
        return (
            f"Frame[{self.nrows}x{self.ncols}] cols={list(zip(names, doms))[:8]}"
            + ("…" if self.ncols > 8 else "")
        )

    # nbytes of device payload (for the materialization-cache cost model)
    def nbytes(self) -> int:
        total = 0
        for c in self.columns:
            total += c.data.size * c.data.dtype.itemsize
            if c.mask is not None:
                total += c.mask.size
        return total


@functools.partial(jax.jit, static_argnums=1)
def _split_device(x, sizes: tuple[int, ...]):
    """``x`` cut into consecutive pieces of ``sizes`` rows, on the device."""
    return jnp.split(x, np.cumsum(sizes[:-1]).tolist())


def _concat_arrays(a, b):
    """Row-axis concat on the host, like :meth:`Column.take`: the output
    length is data-dependent, so a device concatenate would compile a
    program per length pair."""
    return np.concatenate([to_host(a), to_host(b)])


def _set_valid(col: Column, r: int) -> jnp.ndarray | None:
    if col.mask is None:
        return None
    return to_device(col.mask).at[r].set(True)


def _unify_pair(a: Column, b: Column) -> tuple[Column, Column]:
    """Make two columns concatenable: same domain + shared dictionary."""
    if a.domain is b.domain and a.dictionary == b.dictionary:
        return a, b
    if a.domain.is_coded or b.domain.is_coded:
        # Re-encode both against a merged dictionary.
        av, bv = a.to_pylist(), b.to_pylist()
        pa = parse_column([None if v is None else str(v) for v in av], Domain.STR)
        table = list(pa.dictionary or ())
        index = {v: i for i, v in enumerate(table)}
        codes_b = np.zeros(len(bv), dtype=np.int32)
        mask_b = np.ones(len(bv), dtype=np.bool_)
        for i, v in enumerate(bv):
            if v is None:
                codes_b[i] = -1
                mask_b[i] = False
                continue
            key = str(v)
            if key not in index:
                index[key] = len(table)
                table.append(key)
            codes_b[i] = index[key]
        ca = Column(pa.data, Domain.STR, pa.mask, tuple(table))
        cb = Column(
            to_device(codes_b),
            Domain.STR,
            to_device(mask_b) if not mask_b.all() else None,
            tuple(table),
        )
        return ca, cb
    tgt = common_storage([a.domain, b.domain])
    return (
        Column(a.astype_storage(tgt), tgt, a.mask, None),
        Column(b.astype_storage(tgt), tgt, b.mask, None),
    )
