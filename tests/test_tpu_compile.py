"""Compile the product Pallas kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
tiling-unaligned slices, scoped-VMEM overruns, 64-bit types reaching a
kernel. These tests lower and compile each kernel for one chip of a
described `v5e:2x2` topology, with the process-wide x64 mode on as in
production, and assert that the kernel survived into the executable.
Nothing runs; no chip is needed.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU library, and the driver
runs this suite under several xdist workers.
"""

from __future__ import annotations

import functools
import os

import pytest

import jax
import jax.numpy as jnp

import geomesa_tpu.engine.device  # noqa: F401  (turns on x64, as served)

Q = 64            # the chip smoke's kNN query count
N = 1 << 20       # compile time does not depend on the row count


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    from jax.sharding import SingleDeviceSharding

    assert jax.config.jax_enable_x64  # the served mode the kernels face
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compiled_text(fn, *args) -> str:
    """Compile for the described chip; the Pallas kernel must survive
    into the executable as a Mosaic custom call."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_chord_blockmin(spec):
    from geomesa_tpu.engine.knn_scan import chord_blockmin

    _compiled_text(chord_blockmin, spec((Q,)), spec((Q,)), spec((N,)),
                   spec((N,)), spec((N,)))


def test_chord_blockmin_sparse(spec):
    from geomesa_tpu.engine.knn_scan import DATA_TILE, chord_blockmin_sparse

    cap = 64
    assert cap * DATA_TILE <= N
    _compiled_text(chord_blockmin_sparse, spec((Q,)), spec((Q,)),
                   spec((N,)), spec((N,)), spec((N,)),
                   spec((cap,), jnp.int32), spec((), jnp.int32))


@pytest.mark.parametrize("capd", [64, 512])
def test_density_zsparse_call(spec, capd):
    from geomesa_tpu.engine.density_zsparse import (
        CHUNK, DATA_TILE, _zsparse_call)

    tiles = 256
    fn = functools.partial(
        _zsparse_call, capd=capd, bbox=(-180.0, -90.0, 180.0, 90.0),
        width=512, height=512, data_tile=DATA_TILE, chunk=CHUNK,
        interpret=False)
    _compiled_text(fn, spec((N,)), spec((N,)), spec((N,)),
                   spec((tiles,), jnp.int32),
                   spec((tiles, capd), jnp.int32))


def test_pip_sparse_call(spec):
    from geomesa_tpu.engine.pip_sparse import (
        EDGE_TILE, POINT_TILE, _pip_sparse_call)

    n_ptiles, n_etiles, pairs = 2048, 64, 1 << 14
    fn = functools.partial(_pip_sparse_call, n_ptiles=n_ptiles,
                           n_etiles=n_etiles, eps=1e-4, interpret=False)
    e = spec((n_etiles * EDGE_TILE,))
    _compiled_text(fn, spec((n_ptiles * POINT_TILE,)),
                   spec((n_ptiles * POINT_TILE,)), e, e, e, e,
                   spec((pairs,), jnp.int32), spec((pairs,), jnp.int32))


def test_points_in_polygon_pallas(spec):
    from geomesa_tpu.engine.pip_pallas import points_in_polygon_pallas

    e = spec((4096,))
    _compiled_text(points_in_polygon_pallas, spec((N,)), spec((N,)),
                   e, e, e, e)

