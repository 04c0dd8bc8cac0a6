"""Density rasterization for extended geometries (lines / polygons).

Parity: geomesa-index-api DensityScan rasterizes non-point geometries into
the weight grid (SURVEY.md:258-259, C8) [upstream, unverified] — round 1
binned only a representative point per feature; these kernels close that
gap with TPU-first formulations (no per-feature control flow, static
shapes, one scatter + one cumsum instead of per-geometry rasterizer
loops):

- **Lines** (`line_density`): EXACT length-proportional apportioning. A
  feature's weight is distributed over cells proportional to the planar
  length of its path inside each cell, normalized by the feature's total
  planar length. Per segment, the cell-boundary crossings are parametric
  t-values forming two arithmetic sequences (vertical/horizontal grid
  lines); sorting the fixed-size t-array and scattering midpoint cells
  with dt-weights rasterizes every segment in one vectorized pass.
  Segments are Liang-Barsky-clipped to the envelope first so the static
  crossing budget k is bounded by the grid diagonal, not the data extent.

- **Polygons** (`polygon_density`): cell-center coverage — a cell receives
  the feature's full weight iff its center lies inside the polygon
  (holes excluded). Instead of per-polygon parity tests, the kernel
  exploits winding numbers over the ORIENTED flat edge table
  (core.columnar.EdgeTable guarantees shells CCW / holes CW): for a cell
  center p, sum over ALL edges of signed ray crossings s·w equals
  Σ_f w_f·winding_f(p) = Σ_f w_f·inside_f(p) — per-feature grouping
  disappears. Per edge and spanned grid row, the crossing column is
  scattered once into an [H, W+1] accumulator; a reversed exclusive
  row-cumsum then materializes "all cells left of the crossing" — total
  work O(E·rows_spanned + H·W) instead of O(E·H·W).

- **MultiPoint** (via `density_grid_geometry`): every vertex scatters the
  feature's full weight (each constituent point is an observation).

Self-intersecting polygons have winding ≠ parity and are out of contract
(the reference's JTS would reject them as invalid).

Static sizing (`k`) comes from host-side NumPy over the host edge table —
geometry is static per superbatch, so jit cache keys are stable across
queries at a fixed grid/bbox.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from jax import shard_map as _shard_map
import numpy as np

BBox = Tuple[float, float, float, float]

_DEF_TILE_BUDGET = 1 << 22  # elements per [seg_tile, k] tile block


def _seg_tile(k: int) -> int:
    t = _DEF_TILE_BUDGET // max(k, 1)
    t = 1 << (int(t).bit_length() - 1)
    return int(min(max(t, 256), 8192))


def _clip_np(x1, y1, x2, y2, bbox):
    """Host Liang-Barsky: clipped (t0, t1, ok) per segment (f64 NumPy)."""
    xmin, ymin, xmax, ymax = bbox
    ddx, ddy = x2 - x1, y2 - y1
    t0 = np.zeros_like(x1)
    t1 = np.ones_like(x1)
    ok = np.ones(len(x1), dtype=bool)
    for p, q in (
        (-ddx, x1 - xmin),
        (ddx, xmax - x1),
        (-ddy, y1 - ymin),
        (ddy, ymax - y1),
    ):
        r = q / np.where(p == 0, 1.0, p)
        t0 = np.where(p < 0, np.maximum(t0, r), t0)
        t1 = np.where(p > 0, np.minimum(t1, r), t1)
        ok &= ~((p == 0) & (q < 0))
    ok &= t0 <= t1
    return t0, t1, ok


def line_crossing_bounds(
    x1, y1, x2, y2, bbox: BBox, width: int, height: int
) -> Tuple[int, int]:
    """Host: max vertical/horizontal grid-line crossings of any clipped
    segment — the static (kx, ky) budget for `line_density`."""
    if len(x1) == 0:
        return 1, 1
    xmin, ymin, xmax, ymax = bbox
    dx = (xmax - xmin) / width
    dy = (ymax - ymin) / height
    t0, t1, ok = _clip_np(x1, y1, x2, y2, bbox)
    ddx, ddy = x2 - x1, y2 - y1
    xa, xb = x1 + t0 * ddx, x1 + t1 * ddx
    ya, yb = y1 + t0 * ddy, y1 + t1 * ddy
    nx = np.floor((np.maximum(xa, xb) - xmin) / dx) - np.floor(
        (np.minimum(xa, xb) - xmin) / dx
    )
    ny = np.floor((np.maximum(ya, yb) - ymin) / dy) - np.floor(
        (np.minimum(ya, yb) - ymin) / dy
    )
    nx = np.where(ok, nx, 0)
    ny = np.where(ok, ny, 0)
    return int(max(nx.max(), 1)), int(max(ny.max(), 1))


def polygon_rowspan_bound(y1, y2, bbox: BBox, height: int) -> int:
    """Host: max grid rows spanned by any edge (clipped to the envelope) —
    the static k budget for `polygon_density`."""
    if len(y1) == 0:
        return 1
    _, ymin, _, ymax = bbox
    dy = (ymax - ymin) / height
    ylow = np.minimum(y1, y2)
    yhigh = np.maximum(y1, y2)
    rlo = np.maximum(np.ceil((ylow - ymin) / dy - 0.5), 0.0)
    rhi = np.minimum(np.ceil((yhigh - ymin) / dy - 0.5), float(height))
    return int(max((rhi - rlo).max(), 1))


@functools.partial(
    jax.jit,
    static_argnames=("bbox", "width", "height", "kx", "ky", "seg_tile"),
)
def line_density(
    x1: jax.Array,
    y1: jax.Array,
    x2: jax.Array,
    y2: jax.Array,
    wseg: jax.Array,
    segmask: jax.Array,
    bbox: BBox,
    width: int,
    height: int,
    kx: int,
    ky: int,
    seg_tile: int = 2048,
) -> jax.Array:
    """Exact length-proportional line rasterization -> [height, width] f32.

    `wseg` is the per-segment weight DENSITY factor: contribution of a
    t-interval dt inside one cell is wseg * dt, so callers pass
    w_feature * seg_len / total_feature_len for the documented semantics.
    """
    xmin, ymin, xmax, ymax = bbox
    dx = (xmax - xmin) / width
    dy = (ymax - ymin) / height
    f32 = jnp.float32
    n = x1.shape[0]
    pad = (-n) % seg_tile
    arrs = [
        jnp.pad(a.astype(f32), (0, pad)).reshape(-1, seg_tile)
        for a in (x1, y1, x2, y2, wseg)
    ]
    mp = jnp.pad(segmask, (0, pad)).reshape(-1, seg_tile)

    jx = jnp.arange(kx, dtype=f32)
    jy = jnp.arange(ky, dtype=f32)

    def tile(grid, args):
        ax1, ay1, ax2, ay2, w, m = args
        ddx = ax2 - ax1
        ddy = ay2 - ay1
        # Liang-Barsky clip to the envelope
        t0 = jnp.zeros_like(ax1)
        t1 = jnp.ones_like(ax1)
        ok = m
        for p, q in (
            (-ddx, ax1 - xmin),
            (ddx, xmax - ax1),
            (-ddy, ay1 - ymin),
            (ddy, ymax - ay1),
        ):
            r = q / jnp.where(p == 0, 1.0, p)
            t0 = jnp.where(p < 0, jnp.maximum(t0, r), t0)
            t1 = jnp.where(p > 0, jnp.minimum(t1, r), t1)
            ok = ok & ~((p == 0) & (q < 0))
        ok = ok & (t0 <= t1)
        t1c = jnp.maximum(t1, t0)

        # crossing t-values with vertical / horizontal grid lines: two
        # arithmetic sequences over the CLIPPED coordinate span, each t
        # computed against the ORIGINAL segment parameterization; invalid
        # slots park at t1 (zero-length intervals contribute nothing)
        def crossings(lo, hi, orig, delta, start, step, jj):
            i_first = jnp.floor((lo - start) / step) + 1.0
            cnt = jnp.floor((hi - start) / step) - i_first + 1.0
            line = start + (i_first[:, None] + jj[None, :]) * step
            t = (line - orig[:, None]) / jnp.where(delta == 0, 1.0, delta)[
                :, None
            ]
            return jnp.where(jj[None, :] < cnt[:, None], t, t1c[:, None])

        xa = ax1 + t0 * ddx
        xb = ax1 + t1c * ddx
        ya = ay1 + t0 * ddy
        yb = ay1 + t1c * ddy
        tx = crossings(
            jnp.minimum(xa, xb), jnp.maximum(xa, xb), ax1, ddx, xmin, dx, jx
        )
        ty = crossings(
            jnp.minimum(ya, yb), jnp.maximum(ya, yb), ay1, ddy, ymin, dy, jy
        )
        ts = jnp.concatenate(
            [t0[:, None], t1c[:, None], tx, ty], axis=1
        )  # [T, kx+ky+2]
        ts = jnp.clip(ts, t0[:, None], t1c[:, None])
        ts = jnp.sort(ts, axis=1)
        dt = jnp.diff(ts, axis=1)
        tm = (ts[:, 1:] + ts[:, :-1]) * 0.5
        xm = ax1[:, None] + tm * ddx[:, None]
        ym = ay1[:, None] + tm * ddy[:, None]
        colc = jnp.floor((xm - xmin) / dx).astype(jnp.int32)
        rowc = jnp.floor((ym - ymin) / dy).astype(jnp.int32)
        inb = (
            (colc >= 0)
            & (colc < width)
            & (rowc >= 0)
            & (rowc < height)
            & ok[:, None]
            & (dt > 0)
        )
        wv = jnp.where(inb, w[:, None] * dt, 0.0)
        idx = jnp.where(inb, rowc * width + colc, 0)
        grid = grid.at[idx.reshape(-1)].add(wv.reshape(-1))
        return grid, None

    init = jnp.zeros(height * width, f32)
    grid, _ = jax.lax.scan(tile, init, tuple(arrs) + (mp,))
    return grid.reshape(height, width)


@functools.partial(
    jax.jit, static_argnames=("bbox", "width", "height", "k", "seg_tile")
)
def polygon_density(
    x1: jax.Array,
    y1: jax.Array,
    x2: jax.Array,
    y2: jax.Array,
    wedge: jax.Array,
    edgemask: jax.Array,
    bbox: BBox,
    width: int,
    height: int,
    k: int,
    seg_tile: int = 2048,
) -> jax.Array:
    """Cell-center polygon coverage -> [height, width] f32 grid.

    Requires the oriented edge table (shells CCW, holes CW); `wedge` is the
    owning feature's weight replicated per edge.
    """
    return jnp.maximum(
        _polygon_density_signed(
            x1, y1, x2, y2, wedge, edgemask, bbox, width, height, k,
            seg_tile,
        ),
        0.0,
    )


def _polygon_density_signed(
    x1, y1, x2, y2, wedge, edgemask, bbox: BBox,
    width: int, height: int, k: int, seg_tile: int = 2048,
) -> jax.Array:
    """Signed (pre-clamp) winding grid — linear in the edge set."""
    xmin, ymin, xmax, ymax = bbox
    dx = (xmax - xmin) / width
    dy = (ymax - ymin) / height
    f32 = jnp.float32
    n = x1.shape[0]
    pad = (-n) % seg_tile
    arrs = [
        jnp.pad(a.astype(f32), (0, pad)).reshape(-1, seg_tile)
        for a in (x1, y1, x2, y2, wedge)
    ]
    mp = jnp.pad(edgemask, (0, pad)).reshape(-1, seg_tile)
    jj = jnp.arange(k, dtype=f32)

    def tile(acc, args):
        ax1, ay1, ax2, ay2, w, m = args
        ddy = ay2 - ay1
        s = jnp.where(ddy > 0, 1.0, -1.0)
        ylow = jnp.minimum(ay1, ay2)
        yhigh = jnp.maximum(ay1, ay2)
        rlo = jnp.maximum(jnp.ceil((ylow - ymin) / dy - 0.5), 0.0)
        rhi = jnp.minimum(
            jnp.ceil((yhigh - ymin) / dy - 0.5), float(height)
        )
        r = rlo[:, None] + jj[None, :]
        valid = (
            (jj[None, :] < (rhi - rlo)[:, None])
            & m[:, None]
            & (ddy != 0)[:, None]
        )
        py = ymin + (r + 0.5) * dy
        t = (py - ay1[:, None]) / jnp.where(ddy == 0, 1.0, ddy)[:, None]
        xc = ax1[:, None] + t * (ax2 - ax1)[:, None]
        # cells with center strictly left of the crossing receive the
        # signed weight: scatter at the crossing column, prefix later
        cmax = jnp.ceil((xc - xmin) / dx - 0.5)
        valid = valid & (cmax >= 1)
        colp = jnp.minimum(cmax, float(width)).astype(jnp.int32)
        rowp = r.astype(jnp.int32)
        wv = jnp.where(valid, (s * w)[:, None], 0.0)
        idx = jnp.where(valid, rowp * (width + 1) + colp, 0)
        acc = acc.at[idx.reshape(-1)].add(wv.reshape(-1))
        return acc, None

    # derive the init from the inputs so it inherits their varying-
    # mesh-axes tag (lax.scan carry typing under shard_map — same trick
    # as engine.knn)
    vzero = jnp.sum(x1[:1].astype(f32) * 0)
    init = jnp.zeros(height * (width + 1), f32) + vzero
    acc, _ = jax.lax.scan(tile, init, tuple(arrs) + (mp,))
    a = acc.reshape(height, width + 1)
    rev = jnp.cumsum(a[:, ::-1], axis=1)[:, ::-1]
    # f32 boundary band (same caveat as engine.pip_pallas): a cell center
    # within ~1e-6 relative of an edge crossing can see one signed
    # contribution flip sides, leaving a spurious ±w residue in that cell.
    # Clamp keeps the grid non-negative; the affected weight mass is
    # bounded by the band width (tested against the f64 oracle as a
    # mismatch-mass fraction, not bitwise).
    # The PRE-clamp grid is linear in the edge set (scatter + cumsum are
    # both linear), which is what lets polygon_density_sharded psum
    # per-shard signed grids and clamp ONCE at the end (polygon_density
    # itself applies the clamp).
    return rev[:, 1:]


def polygon_density_sharded(
    mesh,
    x1: jax.Array,
    y1: jax.Array,
    x2: jax.Array,
    y2: jax.Array,
    wedge: jax.Array,
    edgemask: jax.Array,
    bbox: BBox,
    width: int,
    height: int,
    k: int,
    seg_tile: int = 2048,
) -> jax.Array:
    """polygon_density with the oriented EDGE table sharded over the mesh:
    per-shard signed winding grids psum-merge exactly (the signed grid is
    linear in edges; edges of one polygon may land on different shards),
    clamped once after the merge. Returns the full grid, replicated."""
    import functools as _ft

    from jax.sharding import PartitionSpec as P

    from geomesa_tpu.parallel.mesh import SHARD_AXIS

    @_ft.partial(
        _shard_map,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS),) * 6,
        out_specs=P(),
    )
    def run(a, b, c, d, w, m):
        # per-shard signed grid = polygon_density minus its final clamp:
        # recompute via the public kernel on the shard, minus clamping --
        # the clamp is idempotent on the true grid but NOT linear, so it
        # must not run before the psum. We get the signed grid by running
        # the kernel body with clamping disabled.
        g = _polygon_density_signed(
            a, b, c, d, w, m, bbox, width, height, k, seg_tile
        )
        return jnp.maximum(jax.lax.psum(g, SHARD_AXIS), 0.0)

    return run(x1, y1, x2, y2, wedge, edgemask)


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def density_grid_geometry(
    geom_col,
    dev: dict,
    name: str,
    weights: jax.Array,
    mask: jax.Array,
    bbox: BBox,
    width: int,
    height: int,
) -> jax.Array:
    """Dispatch density rasterization by geometry kind.

    `geom_col` is the HOST GeometryColumn (static sizing source), `dev` the
    device batch carrying the matching CSR/edge arrays, `weights`/`mask`
    per-FEATURE device arrays. Static k budgets are rounded to pow2 so jit
    caches stay warm across small data changes.

    Mixed "Geometry" columns split per base kind (feature_kinds codes) and
    sum the three sub-grids — running everything through the polygon kernel
    would cancel line/point winding contributions to zero.
    """
    kind = geom_col.kind
    if kind in ("Geometry", "GeometryCollection"):
        return _density_mixed(
            geom_col, name, weights, mask, bbox, width, height
        )
    efeat = dev[f"{name}__efeat"]
    ex1, ey1 = dev[f"{name}__ex1"], dev[f"{name}__ey1"]
    ex2, ey2 = dev[f"{name}__ex2"], dev[f"{name}__ey2"]
    et = geom_col.edge_table()
    if "Point" in kind:  # MultiPoint: every vertex scatters full weight
        from geomesa_tpu.engine.density import density_grid

        vfeat = dev[f"{name}__vfeat"]
        verts = dev[f"{name}__verts"]
        return density_grid(
            verts[:, 0],
            verts[:, 1],
            weights[vfeat],
            mask[vfeat],
            bbox,
            width,
            height,
        )
    if "LineString" in kind:
        kx, ky = line_crossing_bounds(
            et.x1, et.y1, et.x2, et.y2, bbox, width, height
        )
        # +1 margin: the host bound is f64, the kernel counts in f32 — a
        # rounding flip at a cell boundary may admit one extra crossing
        kx, ky = _pow2(kx + 1), _pow2(ky + 1)
        seg_len = jnp.hypot(ex2 - ex1, ey2 - ey1)
        # per-batch geometry extents: the feature/segment counts are
        # fixed by the loaded batch (warmed at ingest), not by the
        # request — the rasterizer compiles once per dataset load
        total = jax.ops.segment_sum(
            seg_len, efeat, num_segments=len(geom_col)  # gt: waive GT28
        )
        wseg = (
            weights[efeat]
            * seg_len
            / jnp.where(total == 0, 1.0, total)[efeat]
        )
        return line_density(
            ex1, ey1, ex2, ey2, wseg, mask[efeat],
            bbox, width, height, kx, ky,
            seg_tile=_seg_tile(kx + ky + 2),
        )
    k = _pow2(polygon_rowspan_bound(et.y1, et.y2, bbox, height) + 1)
    return polygon_density(
        ex1, ey1, ex2, ey2, weights[efeat], mask[efeat],
        bbox, width, height, k, seg_tile=_seg_tile(k),
    )


def _density_mixed(
    geom_col, name: str, weights, mask, bbox: BBox, width: int, height: int
):
    """Mixed-kind density: split the host column per base kind (codes
    0-5 -> code % 3), upload each subset's CSR/edge arrays ad hoc, and sum
    the sub-grids. GeometryCollection features (code 6) have no single
    base kind and degrade to representative-point binning — a documented
    approximation, never a silent zero. Mixed layers are rare and small
    relative to the bench paths, so the per-subset host round trip is
    acceptable; homogeneous columns never come through here.
    """
    import dataclasses

    codes = geom_col.feature_kinds
    from geomesa_tpu.engine.density import density_grid

    if codes is None:
        # no per-feature info (e.g. a column built before round 2 and
        # deserialized from a cache): every feature degrades to its
        # representative point rather than silently cancelling to zero
        return density_grid(
            jnp.asarray(geom_col.x, jnp.float32),
            jnp.asarray(geom_col.y, jnp.float32),
            weights,
            mask,
            bbox,
            width,
            height,
        )
    grid = jnp.zeros((height, width), jnp.float32)
    coll = np.nonzero(codes == 6)[0]
    if len(coll):
        jc = jnp.asarray(coll)
        grid = grid + density_grid(
            jnp.asarray(geom_col.x[coll], jnp.float32),
            jnp.asarray(geom_col.y[coll], jnp.float32),
            jnp.take(weights, jc),
            jnp.take(mask, jc),
            bbox,
            width,
            height,
        )
    base = codes % 3
    for code, sub_kind in ((0, "MultiPoint"), (1, "MultiLineString"), (2, "MultiPolygon")):
        idx = np.nonzero((base == code) & (codes != 6))[0]
        if not len(idx):
            continue
        sub = dataclasses.replace(geom_col.take(idx), kind=sub_kind, feature_kinds=None)
        et = sub.edge_table()
        sub_dev = {
            f"{name}__efeat": jnp.asarray(et.efeat, jnp.int32),
            f"{name}__ex1": jnp.asarray(et.x1, jnp.float32),
            f"{name}__ey1": jnp.asarray(et.y1, jnp.float32),
            f"{name}__ex2": jnp.asarray(et.x2, jnp.float32),
            f"{name}__ey2": jnp.asarray(et.y2, jnp.float32),
            f"{name}__vfeat": jnp.asarray(et.vfeat, jnp.int32),
            f"{name}__verts": jnp.asarray(sub.vertices, jnp.float32),
        }
        jidx = jnp.asarray(idx)
        grid = grid + density_grid_geometry(
            sub,
            sub_dev,
            name,
            jnp.take(weights, jidx),
            jnp.take(mask, jidx),
            bbox,
            width,
            height,
        )
    return grid
