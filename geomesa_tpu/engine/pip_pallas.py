"""Pallas TPU kernel: tiled crossing-number point-in-polygon.

Parity role: same predicate as engine.pip.points_in_polygon (the JTS
prepared-geometry intersects analog — SURVEY.md C4/§7 "hardest kernel",
baseline config 2). TPU-first design: the dense lax implementation
materializes the [N, E] crossing matrix in HBM; this kernel streams fixed
[POINT_TILE, EDGE_TILE] blocks through VMEM with a revisited int32
accumulator block, so HBM traffic is O(N + E) instead of O(N·E) and the
VPU stays saturated on elementwise compare/FMA work.

Grid: (point_tiles, edge_tiles), edge axis minor — each point block's
accumulator is initialized at edge step 0 and folded until the last step
(standard Pallas revisited-output accumulation; the sequential TPU grid
guarantees ordering). Padding edges are degenerate (all zeros) and can
never satisfy the half-open crossing rule; padded points are sliced off.

Layout (Mosaic tiling): points ride the LANE axis as [1, POINT_TILE]
blocks and edges ride the SUBLANE axis as [EDGE_TILE, 1] blocks, so the
[EDGE_TILE, POINT_TILE] crossing matrix is a native VPU broadcast
(no relayout) and the per-point count is a sublane-axis reduction. Block
shapes obey the TPU lowering rule (last two dims divisible by (8, 128) or
equal to the array dims: the 1-sized dims equal the array's).

f32 note: edge-crossing comparisons at f32 resolution can flip for points
within ~1e-7 deg of a boundary (documented divergence from the f64 oracle,
same caveat as the lax path)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax import enable_x64 as _enable_x64
import numpy as np

POINT_TILE = 512
EDGE_TILE = 512


def _pip_kernel(px_ref, py_ref, x1_ref, y1_ref, x2_ref, y2_ref, out_ref):
    import jax.experimental.pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    px = px_ref[0]  # [1, P] — points in lanes
    py = py_ref[0]
    x1 = x1_ref[0]  # [E, 1] — edges in sublanes
    y1 = y1_ref[0]
    x2 = x2_ref[0]
    y2 = y2_ref[0]

    # half-open rule: exactly one endpoint strictly above py
    cond = (y1 <= py) != (y2 <= py)          # [E, P] native broadcast
    # dtype-pinned literal: a bare 1.0 traces as weak f64 when the
    # interpret-mode kernel trace runs under the process-wide x64 mode
    # (the enable_x64(False) window only covers the outer trace entry)
    t = (py - y1) / jnp.where(y2 == y1, jnp.ones((), y1.dtype), y2 - y1)
    xc = x1 + t * (x2 - x1)
    partial = jnp.sum((cond & (xc > px)).astype(jnp.int32), axis=0)  # [P]
    out_ref[...] += partial.reshape(out_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def points_in_polygon_pallas(px, py, x1, y1, x2, y2, interpret: bool = False):
    """Crossing-number test [N] points vs [E] edges -> bool [N] (Pallas)."""
    import jax.experimental.pallas as pl

    n = px.shape[0]
    e = x1.shape[0]
    if e == 0:
        return jnp.zeros((n,), bool)
    npad = (-n) % POINT_TILE
    epad = (-e) % EDGE_TILE
    # kernel is f32-only (Mosaic rejects 64-bit operands); f64 callers accept
    # the documented boundary-resolution caveat above
    dt = jnp.float32
    # points: [gp, 1, POINT_TILE] (lane axis); edges: [ge, EDGE_TILE, 1]
    # (sublane axis)
    pxp = jnp.pad(px.astype(dt), (0, npad)).reshape(-1, 1, POINT_TILE)
    pyp = jnp.pad(py.astype(dt), (0, npad)).reshape(-1, 1, POINT_TILE)
    # degenerate zero edges never cross (y1 == y2 fails the half-open rule)
    e1 = jnp.pad(x1.astype(dt), (0, epad)).reshape(-1, EDGE_TILE, 1)
    f1 = jnp.pad(y1.astype(dt), (0, epad)).reshape(-1, EDGE_TILE, 1)
    e2 = jnp.pad(x2.astype(dt), (0, epad)).reshape(-1, EDGE_TILE, 1)
    f2 = jnp.pad(y2.astype(dt), (0, epad)).reshape(-1, EDGE_TILE, 1)

    gp, ge = pxp.shape[0], e1.shape[0]
    point_block = pl.BlockSpec((1, 1, POINT_TILE), lambda i, j: (i, 0, 0))
    edge_block = pl.BlockSpec((1, EDGE_TILE, 1), lambda i, j: (j, 0, 0))

    # Mosaic rejects 64-bit types; trace the kernel with x64 off so index-map
    # and in-kernel literals stay i32/f32 even when the host runs x64 mode.
    with _enable_x64(False):
        counts = pl.pallas_call(
            _pip_kernel,
            grid=(gp, ge),
            in_specs=[point_block, point_block,
                      edge_block, edge_block, edge_block, edge_block],
            out_specs=pl.BlockSpec((1, 1, POINT_TILE), lambda i, j: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((gp, 1, POINT_TILE), jnp.int32),
            interpret=interpret,
        )(pxp, pyp, e1, f1, e2, f2)
    return (counts.reshape(-1)[:n] % 2) == 1


def _pip_band_kernel(
    px_ref, py_ref, x1_ref, y1_ref, x2_ref, y2_ref, out_ref, *, eps: float
):
    """Boundary-ambiguity flags, same streaming-tile shape as _pip_kernel
    (see engine.pip.points_in_polygon_band for the flag rule)."""
    import jax.experimental.pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    px = px_ref[0]
    py = py_ref[0]
    x1 = x1_ref[0]
    y1 = y1_ref[0]
    x2 = x2_ref[0]
    y2 = y2_ref[0]

    # band terms match pip_sparse._crossing_and_band (see its proof)
    near_flat = ((jnp.abs(py - y1) <= eps) & (jnp.abs(py - y2) <= eps)
                 & (px >= jnp.minimum(x1, x2) - eps)
                 & (px <= jnp.maximum(x1, x2) + eps))
    cond = (y1 <= py) != (y2 <= py)
    # dtype-pinned literal: a bare 1.0 traces as weak f64 when the
    # interpret-mode kernel trace runs under the process-wide x64 mode
    # (the enable_x64(False) window only covers the outer trace entry)
    t = (py - y1) / jnp.where(y2 == y1, jnp.ones((), y1.dtype), y2 - y1)
    xc = x1 + t * (x2 - x1)
    err = eps * (1.0 + jnp.abs(x2 - x1) / jnp.maximum(jnp.abs(y2 - y1), eps))
    flag = jnp.sum((near_flat | (cond & (jnp.abs(xc - px) <= err))).astype(jnp.int32), axis=0)
    out_ref[...] += flag.reshape(out_ref.shape)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def points_in_polygon_band_pallas(
    px, py, x1, y1, x2, y2, eps: float = 1e-4, interpret: bool = False
):
    """Streaming-tile boundary-band flags -> bool [N] (Pallas)."""
    import jax.experimental.pallas as pl

    n = px.shape[0]
    e = x1.shape[0]
    if e == 0:
        return jnp.zeros((n,), bool)
    npad = (-n) % POINT_TILE
    epad = (-e) % EDGE_TILE
    dt = jnp.float32
    pxp = jnp.pad(px.astype(dt), (0, npad)).reshape(-1, 1, POINT_TILE)
    pyp = jnp.pad(py.astype(dt), (0, npad), constant_values=1e9).reshape(
        -1, 1, POINT_TILE
    )
    # padding edges sit at y=1e9 so they are never near a real point's y
    # (zero-padded edges would flag every point with |py| <= eps)
    e1 = jnp.pad(x1.astype(dt), (0, epad)).reshape(-1, EDGE_TILE, 1)
    f1 = jnp.pad(y1.astype(dt), (0, epad), constant_values=1e9).reshape(
        -1, EDGE_TILE, 1
    )
    e2 = jnp.pad(x2.astype(dt), (0, epad)).reshape(-1, EDGE_TILE, 1)
    f2 = jnp.pad(y2.astype(dt), (0, epad), constant_values=1e9).reshape(
        -1, EDGE_TILE, 1
    )

    gp, ge = pxp.shape[0], e1.shape[0]
    point_block = pl.BlockSpec((1, 1, POINT_TILE), lambda i, j: (i, 0, 0))
    edge_block = pl.BlockSpec((1, EDGE_TILE, 1), lambda i, j: (j, 0, 0))

    with _enable_x64(False):
        counts = pl.pallas_call(
            functools.partial(_pip_band_kernel, eps=float(eps)),
            grid=(gp, ge),
            in_specs=[point_block, point_block,
                      edge_block, edge_block, edge_block, edge_block],
            out_specs=pl.BlockSpec((1, 1, POINT_TILE), lambda i, j: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((gp, 1, POINT_TILE), jnp.int32),
            interpret=interpret,
        )(pxp, pyp, e1, f1, e2, f2)
    return counts.reshape(-1)[:n] > 0


# threshold below which the dense lax path wins (kernel launch + padding
# overhead dominates when the [N, E] block fits comfortably anyway)
_MIN_WORK = 1 << 22


def use_pallas_pip(n: int, e: int) -> bool:
    return jax.default_backend() == "tpu" and n * max(e, 1) >= _MIN_WORK


def points_in_polygon_np_edges(px, py, x1, y1, x2, y2) -> np.ndarray:
    """NumPy f64 oracle over an explicit edge table (same edge rule)."""
    px = np.asarray(px, np.float64)[:, None]
    py = np.asarray(py, np.float64)[:, None]
    x1 = np.asarray(x1, np.float64)[None, :]
    y1 = np.asarray(y1, np.float64)[None, :]
    x2 = np.asarray(x2, np.float64)[None, :]
    y2 = np.asarray(y2, np.float64)[None, :]
    cond = (y1 <= py) != (y2 <= py)
    t = (py - y1) / np.where(y2 == y1, 1.0, y2 - y1)
    xc = x1 + t * (x2 - x1)
    return (np.sum(cond & (xc > px), axis=1) % 2) == 1
