"""Synthetic tabular data shaped like the paper's NYC-taxicab benchmark, for
the engine's tests, examples and CPU benchmark suites."""
from .synthetic import numeric_matrix_frame, taxi_like_frame  # noqa: F401
