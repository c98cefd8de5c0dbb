"""repro: *Towards Scalable Dataframe Systems* (Petersohn et al., 2020) on
JAX/TPU — a Modin-style partitioned dataframe engine (core/), Pallas kernels
for its hot operators (kernels/), and synthetic taxi-shaped data for its
tests and examples (data/).
"""
__version__ = "0.1.0"
