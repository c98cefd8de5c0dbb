"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes × dtypes (deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.block_transpose import block_transpose
from repro.kernels.onehot_encode import onehot_encode
from repro.kernels.segment_reduce import segment_reduce
from repro.kernels.window_scan import window_scan


@pytest.mark.parametrize("shape", [(8, 128), (100, 37), (257, 129), (5, 1000), (1, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_block_transpose(rng, shape, dtype):
    x = jnp.asarray(rng.standard_normal(shape) * 10).astype(dtype)
    np.testing.assert_array_equal(np.asarray(block_transpose(x)),
                                  np.asarray(ref.transpose(x)))


@pytest.mark.parametrize("m,g", [(64, 4), (1000, 7), (5000, 129), (17, 1)])
@pytest.mark.parametrize("op", ["sum", "count", "min", "max"])
def test_segment_reduce(rng, m, g, op):
    v = jnp.asarray(rng.standard_normal(m).astype(np.float32))
    c = jnp.asarray(rng.integers(-1, g, m).astype(np.int32))
    out = segment_reduce(v, c, g, op)
    exp = ref.segment_reduce(v, c, g, op)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("cols", [1, 3, 7])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_segment_reduce_multicolumn(rng, cols, op):
    m, g = 777, 13
    v = jnp.asarray(rng.standard_normal((m, cols)).astype(np.float32))
    c = jnp.asarray(rng.integers(-1, g, m).astype(np.int32))
    np.testing.assert_allclose(np.asarray(segment_reduce(v, c, g, op)),
                               np.asarray(ref.segment_reduce(v, c, g, op)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m,n", [(64, 1), (1000, 5), (2049, 3), (70000, 2)])
@pytest.mark.parametrize("op", ["cumsum", "cummax", "cummin"])
def test_window_scan(rng, m, n, op):
    x = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(window_scan(x, op)),
                               np.asarray(ref.window_scan(x, op)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m,g", [(32, 4), (500, 13), (1000, 300)])
def test_onehot_encode(rng, m, g):
    c = jnp.asarray(rng.integers(-1, g, m).astype(np.int32))
    np.testing.assert_array_equal(np.asarray(onehot_encode(c, g)),
                                  np.asarray(ref.onehot_encode(c, g)))


def test_ops_dispatch_matches_both_paths(rng, use_pallas_kernels):
    """ops.py with kernels forced == ref path (same API surface)."""
    from repro.kernels import ops
    assert ops.use_pallas()
    x = jnp.asarray(rng.standard_normal((65, 33)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(ops.transpose(x)), np.asarray(x.T))
    v = jnp.asarray(rng.standard_normal(257).astype(np.float32))
    c = jnp.asarray(rng.integers(0, 5, 257).astype(np.int32))
    np.testing.assert_allclose(np.asarray(ops.segment_reduce(v, c, 5, "sum")),
                               np.asarray(ref.segment_reduce(v, c, 5, "sum")),
                               rtol=1e-4, atol=1e-4)
