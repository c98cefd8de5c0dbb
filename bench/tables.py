"""A generated table as host numpy columns, and its two views: the engine's
``Frame`` on the device and the reference's pandas ``DataFrame``.

Each configuration directory holds one generator module per table
(``bench/configs/<config>/<table>.py``) with ``make(rows, seed, config)``
returning a :class:`Table`; :func:`load` finds it by name.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
from pathlib import Path

import numpy as np

CONFIGS = Path(__file__).resolve().parent / "configs"


@dataclasses.dataclass
class Table:
    """Columns in order.  ``data`` holds int32 / float32 values or int32
    codes; ``valid`` the validity mask of each nullable column; ``labels`` the
    code table of each coded column; ``text`` the coded columns that are
    strings rather than categories."""

    data: dict[str, np.ndarray]
    valid: dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    labels: dict[str, tuple] = dataclasses.field(default_factory=dict)
    text: frozenset = frozenset()

    @property
    def rows(self) -> int:
        return len(next(iter(self.data.values())))

    def nbytes(self) -> int:
        return (sum(a.nbytes for a in self.data.values())
                + sum(m.nbytes for m in self.valid.values()))

    def values(self, name: str) -> np.ndarray:
        """Float64 values with NaN at nulls; coded columns decoded to an
        object array with None at nulls."""
        v = self.data[name]
        m = self.valid.get(name)
        if name in self.labels:
            out = np.asarray(self.labels[name], dtype=object)[v]
            if m is not None:
                out = out.copy()
                out[~m] = None
            return out
        out = v.astype(np.float64)
        if m is not None:
            out[~m] = np.nan
        return out

    def pandas(self):
        import pandas as pd
        return pd.DataFrame({n: self.values(n) for n in self.data})

    def frame(self):
        """The engine's ``Frame``, every column on the default device."""
        import jax.numpy as jnp
        from repro.core import Column, Domain, Frame
        from repro.core.labels import RangeLabels, labels_from_values
        cols = []
        for name, v in self.data.items():
            m = self.valid.get(name)
            mask = None if m is None else jnp.asarray(m)
            if name in self.labels:
                dom = Domain.STR if name in self.text else Domain.CATEGORY
                cols.append(Column(jnp.asarray(v), dom, mask, self.labels[name]))
            else:
                dom = Domain.FLOAT if v.dtype.kind == "f" else Domain.INT
                cols.append(Column(jnp.asarray(v), dom, mask))
        return Frame(cols, RangeLabels(self.rows), labels_from_values(list(self.data)))


def config(name: str) -> dict:
    with open(CONFIGS / name / "config.json") as f:
        return json.load(f)


def load(config_name: str, table: str, rows: int, seed: int) -> Table:
    """Generate ``table`` of configuration ``config_name`` with ``rows`` rows
    from ``seed``, by the configuration's own generator module."""
    mod = importlib.import_module(f"bench.configs.{config_name}.{table}")
    return mod.make(rows, seed, config(config_name))


def stream(seed: int, *key: int) -> np.random.Generator:
    """An independent generator for one purpose of one seed."""
    return np.random.default_rng([int(seed), *key])


class Host:
    """The reference's side of a run: the generated tables, their control
    copies (float columns rounded to bfloat16) and pandas views, each built
    once on first use."""

    def __init__(self, generated: dict[str, Table]):
        self._tables = {(n, False): t for n, t in generated.items()}
        self._pandas: dict = {}

    def table(self, name: str, lowp: bool = False) -> Table:
        key = (name, lowp)
        if key not in self._tables:
            from bench.check import lowp as round_lowp
            t = self._tables[(name, False)]
            data = {n: round_lowp(v) if v.dtype.kind == "f" else v
                    for n, v in t.data.items()}
            self._tables[key] = Table(data, t.valid, t.labels, t.text)
        return self._tables[key]

    def pandas(self, name: str, lowp: bool = False):
        key = (name, lowp)
        if key not in self._pandas:
            self._pandas[key] = self.table(name, lowp).pandas()
        return self._pandas[key]
