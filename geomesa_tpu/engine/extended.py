"""Extended-geometry predicate kernels over per-shard CSR tiles.

Parity role: the JTS prepared-geometry predicate evaluation the reference
applies to line/polygon features [upstream, unverified], restated in the
engine's mask-kernel idiom. The residency tier (store.cache._extended_tiles)
hands each chip an offset-rewritten CSR slice of the store's vertex/ring/edge
buffers — [D, vp, 2] vertices, [D, ep] edge tables, pow2-padded per bucket —
and the kernels here evaluate INTERSECTS / DWITHIN-style predicates per
feature with pure segment reductions (no host loop per geometry; that
antipattern is what analysis rule GT28 guards against).

Exactness contract (same shape as the kNN band corrections): the device scan
runs in f32 and ALSO emits a conservative ambiguity band — rows whose
decision could flip under f32 coordinate rounding (boundary-proximate PiP,
near-degenerate orientation tests, distances within meters of the
threshold). Callers re-decide banded rows on host in f64 against the
ORIGINAL geometry via cql.hosteval — the f64 oracle itself — so the final
mask is bit-identical to `eval_filter_host` on every route.

Semantics mirror cql.hosteval._geom_predicate_np / _eval_distance exactly:
  intersects = bbox_overlap AND (any feature vertex in literal OR any
               literal vertex in feature OR any proper edge crossing)
  dwithin    = (min feature-vertex -> literal-segment planar distance <= d)
               OR intersects
with the identical half-open crossing-number edge rule (engine.pip) and the
identical deg_m/coslat planar projection (111_194.9 m per degree).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from geomesa_tpu.engine.pip import (
    BAND_EPS,
    points_in_polygon,
    points_in_polygon_band,
    polygon_edges,
)
from geomesa_tpu.parallel.mesh import SHARD_AXIS
from jax import shard_map as _shard_map

# must equal cql.hosteval._dist_to_segment_arrays_np's constant
DEG_M = 111_194.9

# distance band (meters): dominates the f64->f32 coordinate cast (~2.5 m
# at |lon| <= 180) with a relative term for long-haul thresholds
DIST_BAND_M = 10.0
DIST_BAND_REL = 1e-3

# orientation-test band: |cross| below this coordinate-scaled epsilon may
# flip sign under f32 rounding (3e-5 deg ~ 2x the f32 ulp at 180)
ORIENT_EPS = 3.0e-5


def _cross(ox, oy, px, py, qx, qy):
    return (px - ox) * (qy - oy) - (py - oy) * (qx - ox)


def _cross_eps(ox, oy, px, py, qx, qy):
    return ORIENT_EPS * (
        jnp.abs(px - ox) + jnp.abs(py - oy)
        + jnp.abs(qx - ox) + jnp.abs(qy - oy)
    ) + 1e-12


@functools.partial(
    jax.jit,
    static_argnames=("n_rows", "poly_lit", "poly_a", "want_dist"),
)
def extended_predicate_tile(
    vx, vy, vfeat,
    ex1, ey1, ex2, ey2, efeat,
    bbox,
    lx1, ly1, lx2, ly2,
    lvx, lvy,
    lit_bbox,
    dist_m,
    *,
    n_rows: int,
    poly_lit: bool,
    poly_a: bool,
    want_dist: bool,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """One shard's predicate scan: feature CSR tile vs one literal.

    vx/vy [vp] + vfeat [vp] (pad id = n_rows), edge table [ep] + efeat
    (pad id = n_rows), bbox [n_rows, 4]; literal edges [L], literal
    vertices [Lv], lit_bbox [4] (xmin, ymin, xmax, ymax). Returns
    (bbox_overlap, intersects, band_intersects, dwithin_or_intersects,
    band_dwithin), each bool [n_rows]. Pad rows (NaN bbox) fail every
    comparison; pad vertex/edge slots bucket into segment n_rows and
    are sliced off."""
    ns = n_rows + 1
    eps = jnp.asarray(BAND_EPS, vx.dtype)
    zrows = jnp.zeros((n_rows,), bool)

    ov = (
        (bbox[:, 0] <= lit_bbox[2]) & (bbox[:, 2] >= lit_bbox[0])
        & (bbox[:, 1] <= lit_bbox[3]) & (bbox[:, 3] >= lit_bbox[1])
    )
    bbox_band = (
        (jnp.abs(bbox[:, 0] - lit_bbox[2]) <= eps)
        | (jnp.abs(bbox[:, 2] - lit_bbox[0]) <= eps)
        | (jnp.abs(bbox[:, 1] - lit_bbox[3]) <= eps)
        | (jnp.abs(bbox[:, 3] - lit_bbox[1]) <= eps)
    )

    # feature vertices inside the literal (only meaningful for polygonal
    # literals — hosteval returns all-False otherwise)
    if poly_lit and lx1.shape[0]:
        in_v = points_in_polygon(vx, vy, lx1, ly1, lx2, ly2)
        bd_v = points_in_polygon_band(vx, vy, lx1, ly1, lx2, ly2)
        a_in = jax.ops.segment_max(
            in_v.astype(jnp.int32), vfeat, num_segments=ns)[:n_rows] > 0
        a_band = jax.ops.segment_max(
            bd_v.astype(jnp.int32), vfeat, num_segments=ns)[:n_rows] > 0
    else:
        a_in, a_band = zrows, zrows

    # literal vertices inside the feature: crossing-number counted per
    # feature by a segment_sum over the edge table (identical edge rule
    # to engine.pip, bucketed instead of dense)
    if poly_a and lvx.shape[0] and ex1.shape[0]:
        py = lvy[None, :]
        y1, y2 = ey1[:, None], ey2[:, None]
        x1, x2 = ex1[:, None], ex2[:, None]
        cond = (y1 <= py) != (y2 <= py)
        t = (py - y1) / jnp.where(y2 == y1, 1.0, y2 - y1)
        xc = x1 + t * (x2 - x1)
        contrib = (cond & (xc > lvx[None, :])).astype(jnp.int32)
        cnt = jax.ops.segment_sum(
            contrib, efeat, num_segments=ns)[:n_rows]
        lit_in = jnp.any((cnt % 2) == 1, axis=1)
        near_flat = (
            (jnp.abs(py - y1) <= eps) & (jnp.abs(py - y2) <= eps)
            & (lvx[None, :] >= jnp.minimum(x1, x2) - eps)
            & (lvx[None, :] <= jnp.maximum(x1, x2) + eps)
        )
        err = eps * (
            1.0 + jnp.abs(x2 - x1)
            / jnp.maximum(jnp.abs(y2 - y1), eps)
        )
        near_cross = cond & (jnp.abs(xc - lvx[None, :]) <= err)
        lit_band = jax.ops.segment_max(
            jnp.any(near_flat | near_cross, axis=1).astype(jnp.int32),
            efeat, num_segments=ns)[:n_rows] > 0
    else:
        lit_in, lit_band = zrows, zrows

    # proper edge crossings (strict orientation signs, collinear = no
    # crossing — exactly _segments_cross); any |d| inside its epsilon
    # means the f32 sign is untrustworthy -> band
    if lx1.shape[0] and ex1.shape[0]:
        a1x, a1y = ex1[:, None], ey1[:, None]
        a2x, a2y = ex2[:, None], ey2[:, None]
        b1x, b1y = lx1[None, :], ly1[None, :]
        b2x, b2y = lx2[None, :], ly2[None, :]
        d1 = _cross(b1x, b1y, b2x, b2y, a1x, a1y)
        d2 = _cross(b1x, b1y, b2x, b2y, a2x, a2y)
        d3 = _cross(a1x, a1y, a2x, a2y, b1x, b1y)
        d4 = _cross(a1x, a1y, a2x, a2y, b2x, b2y)
        crossing = ((d1 > 0) != (d2 > 0)) & ((d3 > 0) != (d4 > 0))
        near = (
            (jnp.abs(d1) <= _cross_eps(b1x, b1y, b2x, b2y, a1x, a1y))
            | (jnp.abs(d2) <= _cross_eps(b1x, b1y, b2x, b2y, a2x, a2y))
            | (jnp.abs(d3) <= _cross_eps(a1x, a1y, a2x, a2y, b1x, b1y))
            | (jnp.abs(d4) <= _cross_eps(a1x, a1y, a2x, a2y, b2x, b2y))
        )
        cr = jax.ops.segment_max(
            jnp.any(crossing, axis=1).astype(jnp.int32),
            efeat, num_segments=ns)[:n_rows] > 0
        cr_band = jax.ops.segment_max(
            jnp.any(near, axis=1).astype(jnp.int32),
            efeat, num_segments=ns)[:n_rows] > 0
    else:
        cr, cr_band = zrows, zrows

    its = ov & (a_in | lit_in | cr)
    # a robustly-disjoint bbox cannot flip regardless of component bands
    band_its = bbox_band | (ov & (a_band | lit_band | cr_band))

    if want_dist:
        # min feature-vertex -> literal-segment distance, the hosteval
        # planar projection verbatim (deg_m * coslat per POINT latitude)
        coslat = jnp.cos(jnp.radians(vy))[:, None]
        ax = (lx1[None, :] - vx[:, None]) * DEG_M * coslat
        ay = (ly1[None, :] - vy[:, None]) * DEG_M
        bx = (lx2[None, :] - vx[:, None]) * DEG_M * coslat
        by = (ly2[None, :] - vy[:, None]) * DEG_M
        dx, dy = bx - ax, by - ay
        L2 = jnp.maximum(dx * dx + dy * dy, 1e-12)
        tt = jnp.clip(-(ax * dx + ay * dy) / L2, 0.0, 1.0)
        cx, cy = ax + tt * dx, ay + tt * dy
        dmin_v = jnp.sqrt(jnp.min(cx * cx + cy * cy, axis=1))
        big = jnp.asarray(np.finfo(np.float32).max, dmin_v.dtype)
        dmin = jax.ops.segment_min(
            jnp.where(vfeat < n_rows, dmin_v, big),
            vfeat, num_segments=ns)[:n_rows]
        dw = (dmin <= dist_m) | its
        dband = jnp.asarray(
            DIST_BAND_M, dmin.dtype) + DIST_BAND_REL * dist_m
        band_dw = (jnp.abs(dmin - dist_m) <= dband) | band_its
    else:
        dw, band_dw = zrows, zrows

    return ov, its, band_its, dw, band_dw


def make_extended_sharded(
    mesh: Mesh,
    *,
    n_rows: int,
    poly_lit: bool,
    poly_a: bool,
    want_dist: bool,
    want_count: bool = False,
):
    """shard_map variant: each chip scans ITS CSR tile (leading-axis
    slice of the [D, ...] tile stacks) against the replicated literal;
    outputs stay row-sharded like the store. With `want_count` the
    dispatch also returns the psum'd fused count of f32-intersecting
    valid rows (pre-band-refinement — callers use it only when the band
    comes back empty)."""

    data = tuple(P(SHARD_AXIS) for _ in range(10))  # tiles + bbox + valid
    lit = tuple(P() for _ in range(8))              # literal + dist

    @functools.partial(
        _shard_map,
        mesh=mesh,
        in_specs=data + lit,
        out_specs=(
            (P(SHARD_AXIS),) * 5 + ((P(),) if want_count else ())
        ),
        check_vma=False,
    )
    def run(verts, vfeat, ex1, ey1, ex2, ey2, efeat, bbox, valid,
            pids, lx1, ly1, lx2, ly2, lvx, lvy, lit_bbox, dist_m):
        res = extended_predicate_tile(
            verts[0, :, 0], verts[0, :, 1], vfeat[0],
            ex1[0], ey1[0], ex2[0], ey2[0], efeat[0],
            bbox,
            lx1, ly1, lx2, ly2, lvx, lvy, lit_bbox, dist_m,
            n_rows=n_rows, poly_lit=poly_lit, poly_a=poly_a,
            want_dist=want_dist,
        )
        if not want_count:
            return res
        hit = (res[3] if want_dist else res[1]) & valid & (pids >= 0)
        count = jax.lax.psum(
            jnp.sum(hit, dtype=jnp.int64), SHARD_AXIS)
        return res + (count,)

    return run


# -- host orchestration ------------------------------------------------------


_SUPPORTED_SPATIAL = ("BBOX", "INTERSECTS", "DISJOINT")
_SUPPORTED_DISTANCE = ("DWITHIN", "BEYOND")
_POLY_KINDS = ("Polygon", "MultiPolygon")


def _poly_vertices_np(g) -> np.ndarray:
    return (
        np.concatenate(g.rings, axis=0).astype(np.float64)
        if g.rings else np.zeros((0, 2))
    )


def _literal_arrays(g):
    """Literal geometry -> the exact arrays hosteval's formulas see:
    ring edges (degenerate vertex segments for point-cloud literals,
    mirroring _dist_to_segments_np), vertices, bbox."""
    x1, y1, x2, y2 = polygon_edges(g)
    if len(x1) == 0:
        pts = _poly_vertices_np(g)
        x1 = x2 = pts[:, 0]
        y1 = y2 = pts[:, 1]
    pts = _poly_vertices_np(g)
    return (
        np.asarray(x1, np.float64), np.asarray(y1, np.float64),
        np.asarray(x2, np.float64), np.asarray(y2, np.float64),
        pts[:, 0], pts[:, 1],
        np.asarray(g.bbox, np.float64),
    )


def tile_predicate(f, sb):
    """Single extended spatial/distance predicate, evaluated on the
    mesh's CSR tiles -> exact host bool [N] (f32 scan + f64 band
    refinement via cql.hosteval, so bit-identical to eval_filter_host).
    Returns None when `f` is not a supported single-predicate shape or
    the superbatch carries no tile for its attribute — callers fall
    back to full host evaluation."""
    from geomesa_tpu.cql import ast
    from geomesa_tpu.cql.hosteval import eval_filter_host

    if isinstance(f, ast.SpatialPredicate):
        if f.op not in _SUPPORTED_SPATIAL:
            return None
        want_dist, dist = False, 0.0
    elif isinstance(f, ast.DistancePredicate):
        if f.op not in _SUPPORTED_DISTANCE:
            return None
        want_dist, dist = True, float(f.distance_m)
    else:
        return None
    name = f.prop.name
    if f"{name}__verts" not in getattr(sb, "tiles", {}):
        return None
    col = sb.batch.columns.get(name)
    if col is None or col.is_point or col.feature_kinds is not None:
        # mixed-kind collections need per-feature poly_a: host path
        return None
    g = f.geometry
    d = int(sb.mesh.devices.size)
    n = len(sb.batch)
    n_rows = n // d
    lx1, ly1, lx2, ly2, lvx, lvy, lbb = _literal_arrays(g)
    run = make_extended_sharded(
        sb.mesh,
        n_rows=n_rows,
        poly_lit=g.kind in _POLY_KINDS,
        poly_a=col.kind in _POLY_KINDS,
        want_dist=want_dist,
    )
    t = sb.tiles
    f32 = np.float32
    ov, its, band_its, dw, band_dw = run(
        t[f"{name}__verts"], t[f"{name}__vfeat"],
        t[f"{name}__ex1"], t[f"{name}__ey1"],
        t[f"{name}__ex2"], t[f"{name}__ey2"], t[f"{name}__efeat"],
        sb.dev[f"{name}__bbox"], sb.dev["__valid__"], sb.pids,
        jnp.asarray(lx1, f32), jnp.asarray(ly1, f32),
        jnp.asarray(lx2, f32), jnp.asarray(ly2, f32),
        jnp.asarray(lvx, f32), jnp.asarray(lvy, f32),
        jnp.asarray(lbb, f32), jnp.asarray(dist, f32),
    )
    ov, its, band_its, dw, band_dw = jax.device_get(
        (ov, its, band_its, dw, band_dw))
    if isinstance(f, ast.SpatialPredicate):
        if f.op == "BBOX":
            base, band = ov, band_its
        else:
            base = ~its if f.op == "DISJOINT" else its
            band = band_its
    else:
        base = ~dw if f.op == "BEYOND" else dw
        band = band_dw
    valid = (
        sb.batch.valid if sb.batch.valid is not None
        else np.ones(n, bool)
    )
    mask = np.asarray(base) & valid
    rows = np.nonzero(np.asarray(band) & valid)[0]
    if len(rows):
        # f64 re-decision against the ORIGINAL geometry — hosteval IS
        # the oracle, so banded rows land bit-identical by construction
        mask[rows] = eval_filter_host(f, sb.batch.select(rows))
    return mask


def host_exact_mask(f, sb) -> np.ndarray:
    """Exact (f64-oracle-identical) filter mask for an extended-store
    mesh superbatch, validity folded: the tile kernels when `f` is a
    single supported predicate, full host f64 evaluation otherwise.
    The planner memoizes the row-sharded device copy per (filter,
    superbatch), so either path costs once per manifest snapshot."""
    from geomesa_tpu.cql.hosteval import eval_filter_host

    m = tile_predicate(f, sb)
    if m is None:
        m = eval_filter_host(f, sb.batch)
    return m
