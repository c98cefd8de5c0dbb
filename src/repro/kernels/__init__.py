"""Pallas TPU kernels for the dataframe engine's hot operators (the paper's
Fig. 6 hot-spots):
  block_transpose — TRANSPOSE's per-block tile transpose
  segment_reduce  — GROUPBY(n) aggregation as MXU one-hot matmul
  window_scan     — WINDOW cumulative ops as a blocked carry scan
  onehot_encode   — get_dummies (§2 A1)

``ops`` is the public dispatching surface; ``ref`` holds pure-jnp oracles.
"""
from . import ops, ref  # noqa: F401
