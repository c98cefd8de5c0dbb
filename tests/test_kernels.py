"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes × dtypes (deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.block_transpose import block_transpose
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.linear_scan import linear_scan
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


@pytest.mark.parametrize("t,n", [(16, 8), (300, 17), (1025, 64)])
def test_linear_scan(rng, t, n):
    a = jnp.asarray((rng.random((t, n)) * 0.95).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((t, n)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(linear_scan(a, b)),
                               np.asarray(ref.linear_scan(a, b)),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("m,g", [(32, 4), (500, 13), (1000, 300)])
def test_onehot_encode(rng, m, g):
    c = jnp.asarray(rng.integers(-1, g, m).astype(np.int32))
    np.testing.assert_array_equal(np.asarray(onehot_encode(c, g)),
                                  np.asarray(ref.onehot_encode(c, g)))


@pytest.mark.parametrize("h,sq,sk,d", [(2, 128, 128, 64), (4, 200, 200, 64),
                                       (1, 64, 256, 128), (2, 333, 333, 80)])
@pytest.mark.parametrize("window", [None, 32])
def test_flash_attention(rng, h, sq, sk, d, window):
    q = jnp.asarray(rng.standard_normal((h, sq, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((h, sk, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((h, sk, d)).astype(np.float32))
    out = flash_attention(q, k, v, causal=True, window=window)
    exp = ref.flash_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16(rng):
    h, s, d = 2, 128, 64
    q = jnp.asarray(rng.standard_normal((h, s, d))).astype(jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((h, s, d))).astype(jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((h, s, d))).astype(jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    exp = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(exp, dtype=np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("h,kvh,s,length,d", [(8, 2, 256, 100, 64),
                                              (16, 4, 333, 217, 64),
                                              (4, 4, 128, 128, 128),
                                              (8, 1, 700, 1, 64)])
def test_decode_attention(rng, h, kvh, s, length, d):
    q = jnp.asarray(rng.standard_normal((h, d)).astype(np.float32))
    kc = jnp.asarray(rng.standard_normal((s, kvh, d)).astype(np.float32))
    vc = jnp.asarray(rng.standard_normal((s, kvh, d)).astype(np.float32))
    out = decode_attention(q, kc, vc, length)
    exp = ref.decode_attention(q, kc, vc, length)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-3, atol=2e-3)


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
