"""Density (heatmap) kernels.

Parity: geomesa-index-api DensityScan + geomesa-process DensityProcess
[upstream, unverified]: rasterize matching features into a width x height
weight grid over a query envelope; per-shard partial grids merge by summation.
The reference runs this per tablet server and sums sparse grids client-side;
here it is one masked scatter-add per shard and one psum over ICI
(SURVEY.md §3.5: "the whole server+client merge in two ops").

Weights: uniform 1, a numeric attribute column, or any precomputed array.
Points outside the envelope never contribute (mask AND bounds check), and the
kernel-radius spread (DensityProcess radiusPixels) is applied as a separable
box/gaussian blur on the final grid host-side or via conv on device.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P

from geomesa_tpu.parallel.mesh import SHARD_AXIS

BBox = Tuple[float, float, float, float]


@functools.partial(jax.jit, static_argnames=("width", "height", "bbox"))
def density_grid(
    x: jax.Array,
    y: jax.Array,
    weights: jax.Array,
    mask: jax.Array,
    bbox: BBox,
    width: int,
    height: int,
) -> jax.Array:
    """Masked scatter-add of points into a [height, width] f32 grid.

    Grid cell (row, col) covers
      lon in [xmin + col*dx, xmin + (col+1)*dx), lat analogously, row 0 at
    ymin (south) — callers flip for image rendering.
    """
    xmin, ymin, xmax, ymax = bbox
    dx = (xmax - xmin) / width
    dy = (ymax - ymin) / height
    col = jnp.floor((x - xmin) / dx).astype(jnp.int32)
    row = jnp.floor((y - ymin) / dy).astype(jnp.int32)
    inb = (col >= 0) & (col < width) & (row >= 0) & (row < height) & mask
    # clip so the scatter index is always in range; weight 0 where not inb
    col = jnp.clip(col, 0, width - 1)
    row = jnp.clip(row, 0, height - 1)
    w = jnp.where(inb, weights.astype(jnp.float32), 0.0)
    flat = jnp.zeros(height * width, jnp.float32)
    flat = flat.at[row * width + col].add(w)
    return flat.reshape(height, width)


@functools.partial(
    jax.jit, static_argnames=("width", "height", "bbox", "point_tile")
)
def density_grid_mxu(
    x: jax.Array,
    y: jax.Array,
    weights: jax.Array,
    mask: jax.Array,
    bbox: BBox,
    width: int,
    height: int,
    point_tile: int = 8192,
) -> jax.Array:
    """Density via the MXU: per-tile one-hot matmuls instead of scatter.

    XLA's scatter-add serializes on TPU (~106ms for 4M points at 512x512,
    HBM bound is ~2ms). Reformulated: for a tile of T points,

        grid += onehot_rows[T, H]^T  @  (onehot_cols[T, W] * w[:, None])

    — an outer-product accumulation the systolic array does at matmul rate.
    One-hot entries are exactly representable in bf16; weights are split
    into bf16 hi + lo parts folded into the COLUMN one-hots of a doubled
    tile, so each product is an exact bf16 multiply and the f32 MXU
    accumulator sees w_hi + w_lo ≈ f32(w) per point. The two-term split
    recovers ~16 of f32's 24 mantissa bits (~2^-16 relative error per
    weight); unweighted counts are exact. Callers needing full f32 weight
    fidelity use the scatter path.

    Out-of-envelope or masked points get row index -1: their one-hot row is
    all zero, so they contribute nothing (same exclusion rule as
    `density_grid`).
    """
    xmin, ymin, xmax, ymax = bbox
    dx = (xmax - xmin) / width
    dy = (ymax - ymin) / height
    n = x.shape[0]
    pad = (-n) % point_tile
    xp = jnp.pad(x, (0, pad))
    yp = jnp.pad(y, (0, pad))
    wp = jnp.pad(weights.astype(jnp.float32), (0, pad))
    mp = jnp.pad(mask, (0, pad))

    col = jnp.floor((xp - xmin) / dx).astype(jnp.int32)
    row = jnp.floor((yp - ymin) / dy).astype(jnp.int32)
    inb = (col >= 0) & (col < width) & (row >= 0) & (row < height) & mp
    row = jnp.where(inb, row, -1)  # -1 -> all-zero one-hot row
    col = jnp.where(inb, col, 0)

    w_hi = wp.astype(jnp.bfloat16)
    w_lo = (wp - w_hi.astype(jnp.float32)).astype(jnp.bfloat16)

    iota_h = jnp.arange(height, dtype=jnp.int32)
    iota_w = jnp.arange(width, dtype=jnp.int32)

    def tile(grid, args):
        r, c, hi, lo = args
        rows = (r[:, None] == iota_h[None, :]).astype(jnp.bfloat16)
        cols = (c[:, None] == iota_w[None, :]).astype(jnp.bfloat16)
        # doubled tile: [2T, H] rows against hi- and lo-weighted cols
        rows2 = jnp.concatenate([rows, rows], axis=0)
        cols2 = jnp.concatenate(
            [cols * hi[:, None], cols * lo[:, None]], axis=0
        )
        grid = grid + jax.lax.dot_general(
            rows2, cols2, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return grid, None

    init = jnp.zeros((height, width), jnp.float32)
    grid, _ = jax.lax.scan(
        tile,
        init,
        (
            row.reshape(-1, point_tile),
            col.reshape(-1, point_tile),
            w_hi.reshape(-1, point_tile),
            w_lo.reshape(-1, point_tile),
        ),
    )
    return grid


# one-hot tiles get memory-heavy past this grid edge ([T, 4096] bf16 = 64MB)
_MXU_MAX_EDGE = 4096
_MXU_MIN_POINTS = 1 << 17


def density_grid_auto(
    x, y, weights, mask, bbox, width, height, exact_weights: bool = False
) -> jax.Array:
    """Backend dispatch: the matmul formulation on TPU at scale, the
    scatter path elsewhere (CPU scatter is fine, and small batches don't
    amortize the one-hot construction). `exact_weights` pins the f32
    scatter path (the MXU bf16 hi/lo split carries ~2^-16 relative weight
    error); surfaced as the `density_exact_weights` query hint."""
    if (
        not exact_weights
        and jax.default_backend() == "tpu"
        and x.shape[0] >= _MXU_MIN_POINTS
        and max(width, height) <= _MXU_MAX_EDGE
    ):
        return density_grid_mxu(x, y, weights, mask, bbox, width, height)
    return density_grid(x, y, weights, mask, bbox, width, height)


def density_sharded(
    mesh: Mesh,
    x: jax.Array,
    y: jax.Array,
    weights: jax.Array,
    mask: jax.Array,
    bbox: BBox,
    width: int,
    height: int,
) -> jax.Array:
    """Sharded density: per-shard scatter + psum merge. Returns the full
    [height, width] grid, replicated."""

    @functools.partial(
        _shard_map,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=P(),
    )
    def run(x, y, w, m):
        g = density_grid(x, y, w, m, bbox, width, height)
        return jax.lax.psum(g, SHARD_AXIS)

    return run(x, y, weights, mask)


def make_density_sharded(mesh: Mesh):
    """Registry-compatible builder of the sharded density program
    (docs/SERVING.md "Sharded serving"): per-shard scatter-add + one
    psum over ICI, with bbox/width/height as static arguments so the
    serve path AOT-compiles one executable per (grid, bucket,
    mesh_shape) key instead of retracing the eager `density_sharded`
    closure on every query."""

    def run(x, y, weights, mask, bbox, width, height):
        @functools.partial(
            _shard_map,
            mesh=mesh,
            in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                      P(SHARD_AXIS)),
            out_specs=P(),
        )
        def body(x, y, w, m):
            g = density_grid(x, y, w, m, bbox, width, height)
            return jax.lax.psum(g, SHARD_AXIS)

        return body(x, y, weights, mask)

    return run


def density_grid_slotted(
    x: jax.Array,
    y: jax.Array,
    weights: jax.Array,
    mask: jax.Array,
    bbox_slot: jax.Array,
    width: int,
    height: int,
) -> jax.Array:
    """Slot-parameterized `density_grid`: the query envelope is a
    DEVICE [4] f32 array (xmin, ymin, xmax, ymax) — a ring-slot input —
    instead of a static trace constant, so one long-lived executable
    per (grid shape, bucket) can serve every envelope without a new
    compile per window. GROUNDWORK for a density ring tier
    (docs/SERVING.md "Persistent serve loop" — today's ring dispatches
    kNN windows only; nothing registers this kernel yet).
    Bit-compatibility caveat a future caller MUST gate on: cell edges
    derive from f32 envelope arithmetic here versus the static path's
    python f64-then-f32 folding, so results match the static kernel
    only when the envelope round-trips f32 exactly (the common
    tile-aligned case) — that parity is what
    tests/test_ringloop.py::TestDensitySlotParity pins. Raw (un-jitted)
    on purpose: the ExecutableRegistry's ring tier owns its
    jit/donation wrapping."""
    xmin = bbox_slot[0]
    ymin = bbox_slot[1]
    xmax = bbox_slot[2]
    ymax = bbox_slot[3]
    dx = (xmax - xmin) / width
    dy = (ymax - ymin) / height
    col = jnp.floor((x - xmin) / dx).astype(jnp.int32)
    row = jnp.floor((y - ymin) / dy).astype(jnp.int32)
    inb = (col >= 0) & (col < width) & (row >= 0) & (row < height) & mask
    col = jnp.clip(col, 0, width - 1)
    row = jnp.clip(row, 0, height - 1)
    w = jnp.where(inb, weights.astype(jnp.float32), 0.0)
    flat = jnp.zeros(height * width, jnp.float32)
    flat = flat.at[row * width + col].add(w)
    return flat.reshape(height, width)


@functools.partial(jax.jit, static_argnames=("radius_pixels",))
def gaussian_blur(grid: jax.Array, radius_pixels: int) -> jax.Array:
    """Separable gaussian spread (DensityProcess radiusPixels analog)."""
    if radius_pixels <= 0:
        return grid
    sigma = jnp.float32(max(radius_pixels / 2.0, 0.5))
    r = radius_pixels
    xs = jnp.arange(-r, r + 1, dtype=jnp.float32)
    k = jnp.exp(-0.5 * (xs / sigma) ** 2)
    k = k / k.sum()
    # separable conv via vmap over rows then cols
    conv1 = lambda v: jnp.convolve(v, k, mode="same")
    blurred = jax.vmap(conv1)(grid)
    blurred = jax.vmap(conv1)(blurred.T).T
    return blurred
