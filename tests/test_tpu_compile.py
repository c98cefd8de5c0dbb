"""Compile the engine's Pallas kernels for a described TPU v5e chip.

Nothing here runs on a TPU: the TPU compiler installed with jaxlib compiles
for a chip that is described, not attached, and refuses what the chip would
refuse (unsupported Mosaic lowerings, VMEM overruns, bad tilings) — things
interpret mode never sees.  Each compile must contain a Mosaic
``tpu_custom_call``; an interpret-mode lowering would compile to plain XLA
and prove nothing.

Shapes are the per-block shapes of ``chip_smoke.py`` at its default scale:
10,000,000 rows over 13 pool workers, and the 200,000 x 32 transpose frame.
The topology is described inside a fixture (never at import), because only
one process at a time may load the TPU library.
"""
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.block_transpose import block_transpose
from repro.kernels.onehot_encode import onehot_encode
from repro.kernels.segment_reduce import segment_reduce
from repro.kernels.window_scan import window_scan

BLOCK_ROWS = 769_231          # ceil(10_000_000 / 13)
TRANSPOSE_BLOCK = (15_385, 32)  # ceil(200_000 / 13) rows of the 32-col matrix
GROUPS = 6                    # passenger_count 1..6
ENGINE_KERNELS = ("block_transpose", "onehot_encode", "segment_reduce",
                  "window_scan")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Lower the kernels through Mosaic (interpret mode off, which the CPU
    backend would pick), route ``kernels.ops`` to them, and keep the
    compiles out of any persistent cache (an entry compiled for an absent
    chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    for name in ENGINE_KERNELS:
        monkeypatch.setattr(importlib.import_module(f"repro.kernels.{name}"),
                            "use_interpret", lambda: False)
    monkeypatch.setenv("REPRO_USE_KERNELS", "1")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()   # the kernel wrappers are jitted: drop traces made
    yield                # in interpret mode
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    monkeypatch.undo()
    jax.clear_caches()   # no Mosaic-lowered trace may serve a later CPU call


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("cols", [1, 8])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_segment_reduce_compiles(mosaic, one_chip, op, cols):
    shape = (BLOCK_ROWS,) if cols == 1 else (BLOCK_ROWS, cols)
    _assert_mosaic(lambda v, c: segment_reduce(v, c, GROUPS, op),
                   _spec(shape, jnp.float32, one_chip),
                   _spec((BLOCK_ROWS,), jnp.int32, one_chip))


@pytest.mark.parametrize("op", ["cumsum", "cummax", "cummin"])
def test_window_scan_compiles(mosaic, one_chip, op):
    _assert_mosaic(lambda x: window_scan(x, op),
                   _spec((BLOCK_ROWS,), jnp.float32, one_chip))


def test_onehot_encode_compiles(mosaic, one_chip):
    _assert_mosaic(lambda c: onehot_encode(c, 3),
                   _spec((BLOCK_ROWS,), jnp.int32, one_chip))


def test_block_transpose_compiles(mosaic, one_chip):
    _assert_mosaic(block_transpose,
                   _spec(TRANSPOSE_BLOCK, jnp.float32, one_chip))


def test_groupby_partial_program_compiles(mosaic, one_chip):
    """The engine's whole per-block partial-aggregation program, as
    ``physical._block_partial`` calls it for sum/mean/count/min/max."""
    bases = ("sum", "count", "min", "count", "max", "count")
    vals = [_spec((BLOCK_ROWS,), jnp.float32, one_chip) for _ in bases]
    valids = [_spec((BLOCK_ROWS,), jnp.bool_, one_chip) for _ in bases]
    codes = _spec((BLOCK_ROWS,), jnp.int32, one_chip)
    lowered = ops._segment_reduce_multi_prog.lower(
        vals, valids, codes, bases=bases, num_segments=GROUPS,
        presence=True, pallas=True)
    assert "tpu_custom_call" in lowered.compile().as_text()
