"""Synthetic tabular data shaped like the paper's NYC-taxicab benchmark (for
the dataframe benchmarks — Fig. 6 uses taxi trips replicated 1–11×)."""
from __future__ import annotations

import numpy as np

from ..core.dtypes import Domain
from ..core.frame import Frame


def taxi_like_frame(n_rows: int, seed: int = 0, n_float_cols: int = 6) -> Frame:
    """Columns mirroring the paper's benchmark data: a small-cardinality
    group key ("passenger_count"), floats with nulls, and a category."""
    rng = np.random.default_rng(seed)
    data = {
        "passenger_count": rng.integers(1, 7, n_rows).tolist(),
        "payment_type": rng.choice(["card", "cash", "dispute"], n_rows).tolist(),
    }
    for j in range(n_float_cols):
        col = rng.standard_normal(n_rows)
        nulls = rng.random(n_rows) < 0.01
        vals = [None if nulls[i] else float(col[i]) for i in range(n_rows)]
        data[f"f{j}"] = vals
    return Frame.from_pydict(data)


def numeric_matrix_frame(n_rows: int, n_cols: int, seed: int = 0) -> Frame:
    """Homogeneous float frame (matrix dataframe) — the transpose benchmark."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    mat = jnp.asarray(rng.standard_normal((n_rows, n_cols)).astype(np.float32))
    return Frame.from_matrix(mat, Domain.FLOAT)
