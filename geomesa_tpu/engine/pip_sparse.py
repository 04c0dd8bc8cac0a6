"""Sparse pair-list point-in-polygon-LAYER: the config-2 spatial join.

Parity role: `Within()` over an OSM-admin-style polygon LAYER x point
events (BASELINE.json config 2; upstream: geomesa's Z2/XZ2 index scan +
JTS prepared-geometry per candidate — SURVEY.md §3.2). The reference
prunes candidates per polygon through the key-value index; the TPU-native
equivalent prunes (point-tile x edge-tile) PAIRS on the host from the
store's Z-order and lets a scalar-prefetched Pallas kernel stream only
the surviving pairs.

Geometry of the pruning (why skipping whole polygons is exact): the
crossing-number ray runs to +x. A CLOSED ring never containing the point
crosses the ray an even number of times, so parity is unchanged if every
edge of that ring is dropped TOGETHER. Hence:
  - polygons whose bbox misses the point tile's bbox are dropped whole;
  - for polygons kept, an edge TILE is dropped only when it provably adds
    zero crossings for every point in the tile (no y-overlap, or entirely
    left of the tile) — this never splits a ring's parity.
To keep "whole polygon" well-defined at tile granularity, the edge table
pads each polygon to a multiple of EDGE_TILE with degenerate edges
(y1 == y2 == BIG: never cross, never flag).

Union semantics: the layer's total crossing parity equals point-in-union
for DISJOINT polygons (admin boundaries; containment count <= 1). Holes
are interior rings in the same table (parity cancels). Overlapping
polygons would need per-polygon parity — documented non-goal here.

f32 boundary: a companion band kernel (same pair list) flags points whose
result is ambiguous at f32 resolution; callers re-evaluate flagged points
exactly in f64 on the host (cql.hosteval pattern). The refinement uses
the SAME pair list, so its candidate set is identical.
"""

from __future__ import annotations

import functools
import threading
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from jax import enable_x64 as _enable_x64
import numpy as np

POINT_TILE = 512
# 512-edge tiles: per-program cost is DMA-latency-bound (~25 us whether
# the fetch is 128 or 512 edges — measured: pair and grouped kernels both
# ~11-14 s over 409k programs at 128), so bigger tiles cut program count
# 4x for ~18% polygon-padding overhead
EDGE_TILE = 512
BIG = 1e9  # degenerate-edge y (never crosses, never near a real point)


class PairList(NamedTuple):
    """Host-built sparse join structure (all numpy)."""

    pair_pt: np.ndarray     # [M] point-tile id per pair (sorted)
    pair_et: np.ndarray     # [M] edge-tile id per pair
    first: np.ndarray       # [M] 1 where a new point tile starts
    covered: np.ndarray     # [n_ptiles] bool: tile appears in >=1 pair
    n_ptiles: int
    n_etiles: int


def _group_ids(ids: np.ndarray):
    """(unique_ids, counts, order): group ANY int id array (sparse,
    large, unsorted — the public contract; a bincount here would
    allocate O(max id) and reject negatives, round-4 review) with an
    O(n) run-length fast path for already-sorted input (every generator
    and the columnar edge table emit sorted ids). `order` sorts ids
    grouped (slice(None) when already sorted)."""
    ids = np.asarray(ids, np.int64)
    if bool((np.diff(ids) >= 0).all()):
        order = slice(None)
        s = ids
    else:
        order = np.argsort(ids, kind="stable")
        s = ids[order]
    if not len(s):
        return s, np.zeros(0, np.int64), order
    starts = np.concatenate([[0], np.nonzero(np.diff(s))[0] + 1])
    counts = np.diff(np.concatenate([starts, [len(s)]]))
    return s[starts], counts, order


def pad_polygon_edges(
    x1, y1, x2, y2, poly_of_edge
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Pad the concatenated oriented edge table so each polygon occupies
    whole EDGE_TILE tiles (degenerate BIG edges fill the tail). Returns
    (x1, y1, x2, y2, poly_of_tile [n_etiles] — ORIGINAL polygon ids).

    Fully vectorized: the round-3 bench measured the per-polygon python
    loop at ~100 s over 10k polygons x 1.5M edges (each iteration scanned
    the whole edge table); this is one (skippable) sort + one scatter."""
    poly_of_edge = np.asarray(poly_of_edge, np.int64)
    pids, counts, order = _group_ids(poly_of_edge)
    padded_counts = -(-counts // EDGE_TILE) * EDGE_TILE
    total = int(padded_counts.sum())
    starts = np.concatenate([[0], np.cumsum(padded_counts)[:-1]])
    # destination of each (pid-sorted) edge = its polygon's padded start
    # + rank within the polygon
    src_starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    rank = np.arange(len(poly_of_edge)) - np.repeat(src_starts, counts)
    dest = np.repeat(starts, counts) + rank
    outs = []
    for arr, fill in zip((x1, y1, x2, y2), (0.0, BIG, 0.0, BIG)):
        # x slots of degenerate edges are logically dead (the y-based
        # crossing test gates them out) but MUST hold finite values:
        # uninitialized garbage flowed into the f64 refine arithmetic and
        # the f32 upload, raising overflow warnings (round-4 review)
        buf = np.full(total, fill, np.float64)
        buf[dest] = np.asarray(arr, np.float64)[order]
        outs.append(buf)
    tiles_per = padded_counts // EDGE_TILE
    poly_of_tile = np.repeat(pids, tiles_per)
    return (*outs, poly_of_tile)


def _cumsum0(counts):
    return np.concatenate([[0], np.cumsum(counts)[:-1]])


def _expand_ranges(starts, counts):
    """[sum(counts)] indices: for each i, starts[i] .. starts[i]+counts[i]."""
    total = int(counts.sum())
    rank = np.arange(total) - np.repeat(_cumsum0(counts), counts)
    return np.repeat(starts, counts) + rank


def build_pairs(
    ptile_bbox: np.ndarray,   # [T, 4] xmin,ymin,xmax,ymax per point tile
    etile_bbox: np.ndarray,   # [E, 4] per edge tile (degenerates excluded)
    poly_of_tile: np.ndarray,  # [E] owning polygon per edge tile
    poly_bbox: np.ndarray,    # [P, 4]
    margin: float = 1e-3,
) -> PairList:
    """Bbox-prune (point tile x edge tile) pairs, polygon-atomically.

    Pair (T, et) survives iff bbox(poly(et)) intersects bbox(T) (expanded
    by `margin` for the f32 band) AND et y-overlaps T AND et is not
    entirely LEFT of T (the +x crossing ray can never reach a tile whose
    ex1 < px0; right-side tiles must be kept — the ray points at them.
    Round 3 had this mirrored; rings spanning >1 edge tile lost
    crossings). Sorted by point tile for revisited-output accumulation.

    Fully vectorized (round 4): the per-polygon python loop measured
    3.9 s at 10k polygons — most of the config-2 end-to-end time. Now:
    tiles and polygons expand into bucket-grid (cell, id) pairs, a CSR
    over cells joins them into (polygon, tile) candidates, and the
    per-pair prunes are flat boolean masks."""
    T = ptile_bbox.shape[0]
    E = etile_bbox.shape[0]
    P = poly_bbox.shape[0]
    px0, py0, px1, py1 = (ptile_bbox[:, i] for i in range(4))

    empty = PairList(np.zeros(0, np.int32), np.zeros(0, np.int32),
                     np.ones(0, np.int32), np.zeros(T, bool), T, E)
    if T == 0 or E == 0 or P == 0:
        return empty

    # ---- bucket grid CSR: cell -> point tiles (tiles register in every
    # cell their bbox touches; Z-ordered tiles overwhelmingly span one)
    G = 128
    gx0 = np.clip(((px0 + 180) / 360 * G).astype(np.int64), 0, G - 1)
    gx1 = np.clip(((px1 + 180) / 360 * G).astype(np.int64), 0, G - 1)
    gy0 = np.clip(((py0 + 90) / 180 * G).astype(np.int64), 0, G - 1)
    gy1 = np.clip(((py1 + 90) / 180 * G).astype(np.int64), 0, G - 1)
    w = gx1 - gx0 + 1
    h = gy1 - gy0 + 1
    reps = w * h
    tid = np.repeat(np.arange(T), reps)
    rank = np.arange(int(reps.sum())) - np.repeat(_cumsum0(reps), reps)
    wrep = np.repeat(w, reps)
    cell = ((np.repeat(gx0, reps) + rank % wrep) * G
            + np.repeat(gy0, reps) + rank // wrep)
    order = np.argsort(cell, kind="stable")
    cell_s, tile_s = cell[order], tid[order]
    cell_lo = np.searchsorted(cell_s, np.arange(G * G))
    cell_hi = np.searchsorted(cell_s, np.arange(G * G) + 1)

    # ---- polygons -> covered cells (both ends clamped INTO the grid so
    # out-of-domain bboxes still query the edge cells — round-3 review)
    bx0, by0, bx1, by1 = (poly_bbox[:, i] for i in range(4))
    cx_lo = np.minimum(
        np.maximum(((bx0 - margin + 180) / 360 * G).astype(np.int64), 0),
        G - 1)
    cx_hi = np.maximum(
        np.minimum(((bx1 + margin + 180) / 360 * G).astype(np.int64), G - 1),
        0)
    cy_lo = np.minimum(
        np.maximum(((by0 - margin + 90) / 180 * G).astype(np.int64), 0),
        G - 1)
    cy_hi = np.maximum(
        np.minimum(((by1 + margin + 90) / 180 * G).astype(np.int64), G - 1),
        0)
    pw = cx_hi - cx_lo + 1
    ph = cy_hi - cy_lo + 1
    preps = pw * ph
    pid_c = np.repeat(np.arange(P), preps)
    prank = np.arange(int(preps.sum())) - np.repeat(_cumsum0(preps), preps)
    pwrep = np.repeat(pw, preps)
    pcell = ((np.repeat(cx_lo, preps) + prank % pwrep) * G
             + np.repeat(cy_lo, preps) + prank // pwrep)

    # ---- CSR join: (polygon, cell) -> candidate (polygon, tile)
    cnt = cell_hi[pcell] - cell_lo[pcell]
    if cnt.sum() == 0:
        return empty
    cand_poly = np.repeat(pid_c, cnt)
    cand_tile = tile_s[_expand_ranges(cell_lo[pcell], cnt)]
    # dedupe (a tile can reach one polygon through several cells)
    key = np.unique(cand_poly.astype(np.int64) * T + cand_tile)
    cand_poly = (key // T).astype(np.int64)
    cand_tile = (key % T).astype(np.int64)

    # ---- polygon-bbox x tile-bbox filter
    hit = (
        (px1[cand_tile] >= bx0[cand_poly] - margin)
        & (px0[cand_tile] <= bx1[cand_poly] + margin)
        & (py1[cand_tile] >= by0[cand_poly] - margin)
        & (py0[cand_tile] <= by1[cand_poly] + margin)
    )
    cand_poly, cand_tile = cand_poly[hit], cand_tile[hit]
    if not len(cand_poly):
        return empty

    # ---- expand each surviving (polygon, tile) over the polygon's edge
    # tiles (contiguous in poly_of_tile by construction: pad_polygon_edges
    # emits pid-sorted tiles)
    et_lo = np.searchsorted(poly_of_tile, cand_poly, side="left")
    et_hi = np.searchsorted(poly_of_tile, cand_poly, side="right")
    ecnt = et_hi - et_lo
    pair_pt = np.repeat(cand_tile, ecnt)
    pair_et = _expand_ranges(et_lo, ecnt)

    # ---- per-pair y-overlap + not-entirely-left prune (degenerate-only
    # tiles carry +-inf bboxes and fail the y test)
    ex1b = etile_bbox[pair_et, 2]
    ey0b = etile_bbox[pair_et, 1]
    ey1b = etile_bbox[pair_et, 3]
    keep = (
        (py1[pair_pt] >= ey0b - margin) & (py0[pair_pt] <= ey1b + margin)
        & (px0[pair_pt] <= ex1b + margin)
    )
    pt = pair_pt[keep]
    et = pair_et[keep]

    order = np.argsort(pt, kind="stable")
    pt, et = pt[order], et[order]
    first = np.ones(len(pt), np.int32)
    first[1:] = (pt[1:] != pt[:-1]).astype(np.int32)
    covered = np.zeros(T, bool)
    covered[pt] = True
    return PairList(pt.astype(np.int32), et.astype(np.int32), first,
                    covered, T, E)


def _crossing_and_band(px, py, x1, y1, x2, y2, eps: float):
    """Shared predicate math for the PIP kernel bodies: returns
    (crossing bool [E, P], band-flag bool [E, P]).

    Why the flag needs NO general endpoint-y strip (round 5; the old
    `|py - y_end| <= eps` term flagged 23% of config-2 points — a
    horizontal strip across the whole tile per endpoint — and made the
    host f64 refine the first-query bottleneck): f32 evaluation computes
    the EXACT even-odd parity of a perturbed polygon. Each vertex
    comparison `(V.y <= py)` is computed bit-identically by both edges
    incident to V (rings are closed; both store the same f32 V), so a
    rounding flip moves V to the other side of the ray CONSISTENTLY —
    pass-through vertices still count once, extrema 0 or 2. Parity of
    the perturbed polygon differs from the true one only for points
    within the perturbation distance of the BOUNDARY, which two cheap
    local tests cover exactly:
      1. `cond & |xc - px| <= err` — horizontal proximity to the edge's
         ray crossing, with `err` inflated by the slope so y-rounding of
         a shallow edge (dxc = slope * dy) stays inside the band;
      2. `near_flat` — an edge whose BOTH endpoint ys are within eps of
         py can have its two comparisons flip independently (the
         vertex-consistency argument couples comparisons across edges,
         not within one); that edge is then near-horizontal at py, so
         the affected points lie inside its eps-inflated bbox — flag
         exactly those, not the whole strip.
    Points outside both bands provably match the f64 oracle; flagged
    points are re-evaluated in f64 by _refine_band_f64."""
    cond = (y1 <= py) != (y2 <= py)
    # dtype-pinned literal: a bare 1.0 traces as weak f64 when the
    # interpret-mode kernel trace is deferred past the enable_x64(False)
    # window, and the while-loop lowering rejects the f64/f32 mix
    t = (py - y1) / jnp.where(y2 == y1, jnp.ones((), y1.dtype), y2 - y1)
    xc = x1 + t * (x2 - x1)
    err = eps * (1.0 + jnp.abs(x2 - x1)
                 / jnp.maximum(jnp.abs(y2 - y1), eps))
    near_flat = (
        (jnp.abs(py - y1) <= eps) & (jnp.abs(py - y2) <= eps)
        & (px >= jnp.minimum(x1, x2) - eps)
        & (px <= jnp.maximum(x1, x2) + eps)
    )
    return cond & (xc > px), near_flat | (cond & (jnp.abs(xc - px) <= err))


def _sparse_kernel(pt_ref, et_ref, px_ref, py_ref,
                   x1_ref, y1_ref, x2_ref, y2_ref, out_ref):
    import jax.experimental.pallas as pl

    m = pl.program_id(0)
    # first-visit detection from the pt scalars themselves (a dedicated
    # flags array would blow the 1 MB SMEM prefetch budget at ~100k pairs)
    prev = pt_ref[jnp.maximum(m - 1, 0)]

    @pl.when((m == 0) | (pt_ref[m] != prev))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    px = px_ref[0]
    py = py_ref[0]
    # edges arrive lane-major ([1, EDGE_TILE]: a [E, 128, 1] layout pads
    # the 1-wide lane dim 128x -> 7 GB/array at 15M edge slots) and are
    # transposed onto sublanes in VMEM for the [E, P] broadcast
    x1 = x1_ref[0].reshape(EDGE_TILE, 1)
    y1 = y1_ref[0].reshape(EDGE_TILE, 1)
    x2 = x2_ref[0].reshape(EDGE_TILE, 1)
    y2 = y2_ref[0].reshape(EDGE_TILE, 1)
    crossing, _ = _crossing_and_band(px, py, x1, y1, x2, y2, 1e-4)
    partial = jnp.sum(crossing.astype(jnp.int32), axis=0)
    out_ref[...] += partial.reshape(out_ref.shape)


def _sparse_band_kernel(pt_ref, et_ref, px_ref, py_ref,
                        x1_ref, y1_ref, x2_ref, y2_ref, out_ref, *,
                        eps: float):
    import jax.experimental.pallas as pl

    m = pl.program_id(0)
    prev = pt_ref[jnp.maximum(m - 1, 0)]

    @pl.when((m == 0) | (pt_ref[m] != prev))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    px = px_ref[0]
    py = py_ref[0]
    x1 = x1_ref[0].reshape(EDGE_TILE, 1)
    y1 = y1_ref[0].reshape(EDGE_TILE, 1)
    x2 = x2_ref[0].reshape(EDGE_TILE, 1)
    y2 = y2_ref[0].reshape(EDGE_TILE, 1)
    _, flag = _crossing_and_band(px, py, x1, y1, x2, y2, eps)
    out_ref[...] += jnp.sum(flag.astype(jnp.int32), axis=0).reshape(
        out_ref.shape)


def _make_multi_kernel(e_per: int, eps: float):
    """Grid (tiles, cap/e_per): program (i, j) folds E_PER edge tiles
    into point tile i's accumulators in ONE program. Each edge tile is a
    SEPARATE scalar-indexed operand, so Mosaic issues their DMAs
    concurrently. Measured on the config-2 layer (v5e, round 4):
    e_per=2 is the sweet spot (0.55 s vs 1.49 s at e_per=1); 4/8 regress
    (~1.1-1.2 s — wider programs starve the double-buffering). The
    decisive round-4 fix was pow2 capacity BUCKETS in the caller, not
    e_per: two coarse classes let one dense tile inflate cap for
    thousands of rows and the pallas call count dominated (6 s)."""

    def _kernel(etab_ref, px_ref, py_ref, *refs):
        import jax.experimental.pallas as pl

        out_ref, band_ref = refs[-2], refs[-1]
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)
            band_ref[...] = jnp.zeros_like(band_ref)

        px = px_ref[0]
        py = py_ref[0]
        for e in range(e_per):
            x1 = refs[4 * e][0].reshape(EDGE_TILE, 1)
            y1 = refs[4 * e + 1][0].reshape(EDGE_TILE, 1)
            x2 = refs[4 * e + 2][0].reshape(EDGE_TILE, 1)
            y2 = refs[4 * e + 3][0].reshape(EDGE_TILE, 1)
            crossing, flag = _crossing_and_band(px, py, x1, y1, x2, y2, eps)
            out_ref[...] += jnp.sum(
                crossing.astype(jnp.int32), axis=0).reshape(out_ref.shape)
            band_ref[...] += jnp.sum(
                flag.astype(jnp.int32), axis=0).reshape(band_ref.shape)

    return _kernel


@functools.partial(
    jax.jit,
    static_argnames=("cap", "n_etiles", "eps", "interpret", "e_per"),
)
def _pip_grouped_call(
    px_cov, py_cov, x1, y1, x2, y2, etab,
    cap: int, n_etiles: int, eps: float, interpret: bool, e_per: int = 2,
):
    """One capacity class: [Tc] gathered point tiles x up to `cap` edge
    tiles each (etab [Tc, cap] i32; entries == n_etiles hit the appended
    all-degenerate dummy tile — the caller appends it ONCE per query).
    cap must be a multiple of e_per (callers pad etab with the dummy).
    Returns (counts [Tc, POINT_TILE], band [Tc, POINT_TILE])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    e_per = min(e_per, cap)
    assert cap % e_per == 0, (cap, e_per)
    dt = jnp.float32
    tc = px_cov.shape[0]
    pxp = px_cov.astype(dt).reshape(tc, 1, POINT_TILE)
    pyp = py_cov.astype(dt).reshape(tc, 1, POINT_TILE)
    e1 = x1.astype(dt).reshape(-1, 1, EDGE_TILE)
    f1 = y1.astype(dt).reshape(-1, 1, EDGE_TILE)
    e2 = x2.astype(dt).reshape(-1, 1, EDGE_TILE)
    f2 = y2.astype(dt).reshape(-1, 1, EDGE_TILE)

    point_block = pl.BlockSpec((1, 1, POINT_TILE), lambda i, j, et: (i, 0, 0))

    def edge_block(e):
        return pl.BlockSpec(
            (1, 1, EDGE_TILE),
            lambda i, j, et, e=e: (et[i, j * e_per + e], 0, 0),
        )

    out_block = pl.BlockSpec((1, 1, POINT_TILE), lambda i, j, et: (i, 0, 0))
    out_shape = jax.ShapeDtypeStruct((tc, 1, POINT_TILE), jnp.int32)

    edge_specs = []
    edge_args = []
    for e in range(e_per):
        edge_specs.extend([edge_block(e)] * 4)
        edge_args.extend([e1, f1, e2, f2])

    with _enable_x64(False):
        counts, band = pl.pallas_call(
            _make_multi_kernel(e_per, eps),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(tc, cap // e_per),
                in_specs=[point_block, point_block] + edge_specs,
                out_specs=(out_block, out_block),
            ),
            out_shape=(out_shape, out_shape),
            interpret=interpret,
        )(etab, pxp, pyp, *edge_args)
    return counts.reshape(tc, POINT_TILE), band.reshape(tc, POINT_TILE)


# SMEM budget: etab is the only prefetched scalar array (4 B/slot); the
# runtime DOUBLE-BUFFERS prefetched operands and row-pads narrow rows,
# so the effective budget is ~2^15 padded slots (256 KB resident)
MAX_ETAB_SLOTS = 1 << 15


def _pow2_caps(counts: np.ndarray) -> np.ndarray:
    """pow2 capacity bucket per tile row (floor 4). Shared by the union
    and assignment drivers: a coarse two-class scheme let one dense tile
    inflate cap for thousands of rows, and the collapsed rows-per-call
    made pallas dispatch count dominate (measured 6 s on the config-2
    layer; bucketing brings total calls to ~total_slots/MAX_ETAB_SLOTS)."""
    return np.maximum(
        2 ** np.ceil(np.log2(np.maximum(counts, 1))).astype(np.int64), 4)


def pip_layer_grouped(
    px, py, x1, y1, x2, y2, pair_pt, pair_et,
    n_ptiles: int = 0, n_etiles: int = 0, eps: float = 1e-4,
    interpret: bool = False, e_per: int = 2,
):
    """Grouped-by-point-tile execution of the pair list (the fast path;
    same result contract as pip_layer_sparse but returns DEVICE arrays).
    Tiles are bucketed into capacity classes (each dispatch has a fixed
    cost, so call count matters more than padding waste); per-call
    results stay on device and scatter into the full outputs — the first
    grouped implementation's per-call host fetches dominated its wall
    time."""
    import jax.numpy as _jnp

    pt_np = np.asarray(pair_pt, np.int64)
    et_np = np.asarray(pair_et, np.int64)
    if not len(pt_np):
        z = _jnp.zeros(n_ptiles * POINT_TILE, _jnp.int32)
        return z, z
    tiles, counts = np.unique(pt_np, return_counts=True)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pxt = _jnp.asarray(px).reshape(n_ptiles, POINT_TILE)
    pyt = _jnp.asarray(py).reshape(n_ptiles, POINT_TILE)
    out_c = _jnp.zeros((n_ptiles, POINT_TILE), _jnp.int32)
    out_b = _jnp.zeros((n_ptiles, POINT_TILE), _jnp.int32)
    # dummy all-BIG edge tile appended ONCE per query (id n_etiles)
    dt32 = _jnp.float32
    ax1 = _jnp.concatenate([_jnp.asarray(x1, dt32),
                            _jnp.zeros(EDGE_TILE, dt32)])
    ay1 = _jnp.concatenate([_jnp.asarray(y1, dt32),
                            _jnp.full(EDGE_TILE, BIG, dt32)])
    ax2 = _jnp.concatenate([_jnp.asarray(x2, dt32),
                            _jnp.zeros(EDGE_TILE, dt32)])
    ay2 = _jnp.concatenate([_jnp.asarray(y2, dt32),
                            _jnp.full(EDGE_TILE, BIG, dt32)])

    from geomesa_tpu.utils.padding import next_pow2 as _np2

    caps_of = _pow2_caps(counts)
    for cap_c in np.unique(caps_of):
        sel = np.nonzero(caps_of == cap_c)[0]
        cap_c = int(cap_c)
        # vectorized etab fill (repeat/rank scatter, same idiom as
        # pad_polygon_edges — a per-row python loop sat in the timed path)
        etab = np.full((len(sel), cap_c), n_etiles, np.int32)
        cnt_s = counts[sel]
        row_of = np.repeat(np.arange(len(sel)), cnt_s)
        col_of = (np.arange(cnt_s.sum())
                  - np.repeat(np.concatenate([[0], np.cumsum(cnt_s)[:-1]]),
                              cnt_s))
        etab[row_of, col_of] = et_np[
            np.repeat(starts[sel], cnt_s) + col_of]
        ptids = tiles[sel]
        # a single row wider than the SMEM budget splits by COLUMN chunks
        # that accumulate (+=) into the same tiles — counts and band
        # flags are both additive across edge-tile subsets
        for k0 in range(0, cap_c, MAX_ETAB_SLOTS):
            sub = etab[:, k0: k0 + MAX_ETAB_SLOTS]
            cap_k = sub.shape[1]
            per_call = max(1, MAX_ETAB_SLOTS // max(cap_k, 32))
            for c0 in range(0, len(sel), per_call):
                c1 = min(c0 + per_call, len(sel))
                ids = ptids[c0:c1]
                tab = np.ascontiguousarray(sub[c0:c1])
                # pow2 tile-count bucket: padding rows reuse a real tile
                # id with an ALL-DUMMY etab row, contributing exact zeros
                # through the scatter-add
                tc_pad = max(_np2(len(ids)), 8) - len(ids)
                if tc_pad:
                    ids = np.concatenate(
                        [ids, np.full(tc_pad, ids[0], ids.dtype)])
                    tab = np.concatenate([
                        tab,
                        np.full((tc_pad, cap_k), n_etiles, np.int32),
                    ])
                jid = _jnp.asarray(ids)
                # per-layer tiling: point/edge tile counts are fixed
                # by the loaded polygon layer (chunks pow2-padded
                # above) — compiles track layer loads, not traffic
                # gt: waive GT28
                cc, bb = _pip_grouped_call(
                    _jnp.take(pxt, jid, axis=0),
                    _jnp.take(pyt, jid, axis=0),
                    ax1, ay1, ax2, ay2,
                    _jnp.asarray(tab),
                    cap=cap_k, n_etiles=n_etiles, eps=eps,
                    interpret=interpret, e_per=e_per,
                )
                out_c = out_c.at[jid].add(cc)
                out_b = out_b.at[jid].add(bb)
    return out_c.reshape(-1), out_b.reshape(-1)


def _make_assign_kernel(e_per: int, eps: float):
    """Per-POLYGON parity (the relation-join kernel): like the union
    kernel, but a running per-point crossing accumulator FLUSHES at each
    polygon boundary (pinfo slot < 0), adding parity * (pid+1) into the
    assignment and parity into the containment count. For a disjoint
    layer, assignment-1 is exactly the containing polygon id (or -1).
    Requires each row's pairs grouped contiguously by polygon — the
    pair list is built that way (build_pairs expands polygon-major)."""

    def _kernel(etab_ref, pinfo_ref, px_ref, py_ref, *refs):
        import jax.experimental.pallas as pl

        assign_ref, count_ref, band_ref, cur_ref = refs[-4:]
        i = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            assign_ref[...] = jnp.zeros_like(assign_ref)
            count_ref[...] = jnp.zeros_like(count_ref)
            band_ref[...] = jnp.zeros_like(band_ref)
            cur_ref[...] = jnp.zeros_like(cur_ref)

        px = px_ref[0]
        py = py_ref[0]
        for e in range(e_per):
            x1 = refs[4 * e][0].reshape(EDGE_TILE, 1)
            y1 = refs[4 * e + 1][0].reshape(EDGE_TILE, 1)
            x2 = refs[4 * e + 2][0].reshape(EDGE_TILE, 1)
            y2 = refs[4 * e + 3][0].reshape(EDGE_TILE, 1)
            crossing, flag = _crossing_and_band(px, py, x1, y1, x2, y2, eps)
            cur_ref[...] += jnp.sum(
                crossing.astype(jnp.int32), axis=0).reshape(cur_ref.shape)
            band_ref[...] += jnp.sum(
                flag.astype(jnp.int32), axis=0).reshape(band_ref.shape)
            info = pinfo_ref[i, j * e_per + e]

            @pl.when(info < 0)
            def _flush(info=info):
                parity = cur_ref[...] & 1
                assign_ref[...] += parity * (-info)
                count_ref[...] += parity
                cur_ref[...] = jnp.zeros_like(cur_ref)

    return _kernel


@functools.partial(
    jax.jit,
    static_argnames=("cap", "n_etiles", "eps", "interpret", "e_per"),
)
def _pip_assign_call(
    px_cov, py_cov, x1, y1, x2, y2, etab, pinfo,
    cap: int, n_etiles: int, eps: float, interpret: bool, e_per: int = 2,
):
    """Assignment-mode capacity class (see _make_assign_kernel). Returns
    (assign, count, band) each [Tc, POINT_TILE] i32. `pinfo[i, j]` is
    pid+1 of the pair's polygon, NEGATED on the last slot of that
    polygon's run in row i, 0 for dummy padding."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    e_per = min(e_per, cap)
    assert cap % e_per == 0, (cap, e_per)
    dt = jnp.float32
    tc = px_cov.shape[0]
    pxp = px_cov.astype(dt).reshape(tc, 1, POINT_TILE)
    pyp = py_cov.astype(dt).reshape(tc, 1, POINT_TILE)
    e1 = x1.astype(dt).reshape(-1, 1, EDGE_TILE)
    f1 = y1.astype(dt).reshape(-1, 1, EDGE_TILE)
    e2 = x2.astype(dt).reshape(-1, 1, EDGE_TILE)
    f2 = y2.astype(dt).reshape(-1, 1, EDGE_TILE)

    point_block = pl.BlockSpec(
        (1, 1, POINT_TILE), lambda i, j, et, pi: (i, 0, 0))

    def edge_block(e):
        return pl.BlockSpec(
            (1, 1, EDGE_TILE),
            lambda i, j, et, pi, e=e: (et[i, j * e_per + e], 0, 0),
        )

    out_block = pl.BlockSpec(
        (1, 1, POINT_TILE), lambda i, j, et, pi: (i, 0, 0))
    out_shape = jax.ShapeDtypeStruct((tc, 1, POINT_TILE), jnp.int32)

    edge_specs = []
    edge_args = []
    for e in range(e_per):
        edge_specs.extend([edge_block(e)] * 4)
        edge_args.extend([e1, f1, e2, f2])

    with _enable_x64(False):
        assign, count, band, _cur = pl.pallas_call(
            _make_assign_kernel(e_per, eps),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(tc, cap // e_per),
                in_specs=[point_block, point_block] + edge_specs,
                out_specs=(out_block, out_block, out_block, out_block),
            ),
            out_shape=(out_shape,) * 4,
            interpret=interpret,
        )(etab, pinfo, pxp, pyp, *edge_args)
    return (assign.reshape(tc, POINT_TILE), count.reshape(tc, POINT_TILE),
            band.reshape(tc, POINT_TILE))


def pip_layer_assign(
    px_np: np.ndarray,
    py_np: np.ndarray,
    x1: np.ndarray,
    y1: np.ndarray,
    x2: np.ndarray,
    y2: np.ndarray,
    poly_of_edge: np.ndarray,
    eps: float = 1e-4,
    interpret: bool = False,
    refine_f64: bool = True,
    prep: "LayerPrep | None" = None,
    poly_of_tile: "np.ndarray | None" = None,
) -> Tuple[np.ndarray, np.ndarray, dict]:
    """Point -> polygon ASSIGNMENT over the layer (the relation-join /
    JoinProcess result shape, SURVEY.md:382-383, 415): returns
    (poly_id [N] int32 — containing polygon id, -1 outside every polygon,
    count [N] int32 — how many polygons contain the point (==1 for
    disjoint layers; >1 reveals overlap, where poly_id is a sum and NOT
    a valid id), info dict). Band-flagged points are re-evaluated in f64
    per candidate polygon on the host (exact assignment)."""
    n = len(px_np)
    if prep is None:
        prep = prepare_layer(px_np, py_np, x1, y1, x2, y2, poly_of_edge)
    pl_ = prep.pairs
    n_ptiles, n_etiles = prep.n_ptiles, prep.n_etiles
    if len(pl_.pair_pt) == 0:
        return (np.full(n, -1, np.int32), np.zeros(n, np.int32),
                {"pairs": 0, "refined": 0})

    import jax.numpy as _jnp
    from geomesa_tpu.utils.padding import next_pow2 as _np2

    # polygon RANKS per edge tile + rank->id mapping (see
    # _poly_of_tile_from) — callers holding one (pip_layer_join) pass it
    if poly_of_tile is None:
        poly_of_tile, poly_uids = _poly_of_tile_from(prep, poly_of_edge)
    else:
        poly_of_tile, poly_uids = poly_of_tile

    pt_np = np.asarray(pl_.pair_pt, np.int64)
    et_np = np.asarray(pl_.pair_et, np.int64)
    pid_np = poly_of_tile[et_np]
    # group each row's pairs by polygon (they are already polygon-major
    # from build_pairs; a stable (pt, pid) sort makes it unconditional)
    order = np.lexsort((pid_np, pt_np))
    pt_np, et_np, pid_np = pt_np[order], et_np[order], pid_np[order]
    # flush marker: last slot of each (tile, polygon) run
    last = np.ones(len(pt_np), bool)
    last[:-1] = (pt_np[1:] != pt_np[:-1]) | (pid_np[1:] != pid_np[:-1])
    pinfo_val = np.where(last, -(pid_np + 1), pid_np + 1).astype(np.int32)

    tiles, counts = np.unique(pt_np, return_counts=True)
    starts = _cumsum0(counts)
    pxt = _jnp.asarray(prep.pxp).reshape(n_ptiles, POINT_TILE)
    pyt = _jnp.asarray(prep.pyp).reshape(n_ptiles, POINT_TILE)
    out_a = np.zeros((n_ptiles, POINT_TILE), np.int32)
    out_n = np.zeros((n_ptiles, POINT_TILE), np.int32)
    out_b = np.zeros((n_ptiles, POINT_TILE), np.int32)
    dt32 = _jnp.float32
    ax1 = _jnp.concatenate([_jnp.asarray(prep.ex1, dt32),
                            _jnp.zeros(EDGE_TILE, dt32)])
    ay1 = _jnp.concatenate([_jnp.asarray(prep.ey1, dt32),
                            _jnp.full(EDGE_TILE, BIG, dt32)])
    ax2 = _jnp.concatenate([_jnp.asarray(prep.ex2, dt32),
                            _jnp.zeros(EDGE_TILE, dt32)])
    ay2 = _jnp.concatenate([_jnp.asarray(prep.ey2, dt32),
                            _jnp.full(EDGE_TILE, BIG, dt32)])

    host_rows = []
    caps_of = _pow2_caps(counts)
    for cap_c in np.unique(caps_of):
        sel = np.nonzero(caps_of == cap_c)[0]
        cap_c = int(cap_c)
        if cap_c > MAX_ETAB_SLOTS // 2:
            # assignment cannot split a row across calls (the running
            # parity would be lost between them): rows this dense are
            # evaluated exactly on the host instead. Half the union
            # budget: this kernel prefetches TWO scalar arrays
            # (etab + pinfo), and SMEM overflowed by 1.2K at the 10k-
            # polygon SQL-join scale when budgeted for one.
            host_rows.extend(tiles[sel].tolist())
            continue
        etab = np.full((len(sel), cap_c), n_etiles, np.int32)
        pinf = np.zeros((len(sel), cap_c), np.int32)
        cnt_s = counts[sel]
        row_of = np.repeat(np.arange(len(sel)), cnt_s)
        col_of = (np.arange(cnt_s.sum()) - np.repeat(_cumsum0(cnt_s), cnt_s))
        src = np.repeat(starts[sel], cnt_s) + col_of
        etab[row_of, col_of] = et_np[src]
        pinf[row_of, col_of] = pinfo_val[src]
        ptids = tiles[sel]
        # half the union kernel's SMEM budget: etab AND pinfo prefetch
        per_call = max(1, (MAX_ETAB_SLOTS // 2) // max(cap_c, 32))
        for c0 in range(0, len(sel), per_call):
            c1 = min(c0 + per_call, len(sel))
            ids = ptids[c0:c1]
            tab = np.ascontiguousarray(etab[c0:c1])
            pin = np.ascontiguousarray(pinf[c0:c1])
            tc_pad = max(_np2(len(ids)), 8) - len(ids)
            if tc_pad:
                ids = np.concatenate([ids, np.full(tc_pad, ids[0], ids.dtype)])
                tab = np.concatenate(
                    [tab, np.full((tc_pad, cap_c), n_etiles, np.int32)])
                pin = np.concatenate(
                    [pin, np.zeros((tc_pad, cap_c), np.int32)])
            jid = _jnp.asarray(ids)
            # cap_c is pow2-bucketed: one trace per bucket, bounded;
            # tile extents are per-layer constants (see grouped path)
            # gt: waive GT28
            aa, nn, bb = _pip_assign_call(  # gt: waive GT01
                _jnp.take(pxt, jid, axis=0), _jnp.take(pyt, jid, axis=0),
                ax1, ay1, ax2, ay2,
                _jnp.asarray(tab), _jnp.asarray(pin),
                cap=cap_c, n_etiles=n_etiles, eps=eps, interpret=interpret,
            )
            la = len(ptids[c0:c1])
            out_a[ptids[c0:c1]] = np.asarray(aa)[:la]
            out_n[ptids[c0:c1]] = np.asarray(nn)[:la]
            out_b[ptids[c0:c1]] = np.asarray(bb)[:la]

    out_a[~pl_.covered] = 0
    out_n[~pl_.covered] = 0
    out_b[~pl_.covered] = 0
    assign = out_a.reshape(-1)[:n]
    count = out_n.reshape(-1)[:n]
    band = out_b.reshape(-1)[:n]
    poly_id = np.where(count == 1, assign - 1, -1).astype(np.int32)

    # host-exact rows: band-flagged points (skippable via refine_f64) +
    # tiles too dense for one call (NEVER skippable — the kernel computed
    # nothing for them, so skipping would silently report every point of
    # the tile as outside; round-4 review)
    refine_idx = np.nonzero(band > 0)[0] if refine_f64 else (
        np.zeros(0, np.int64))
    if host_rows:
        hr = np.concatenate([
            np.arange(t * POINT_TILE, min((t + 1) * POINT_TILE, n))
            for t in host_rows
        ])
        refine_idx = np.unique(np.concatenate([refine_idx, hr]))
    refined = 0
    if len(refine_idx):
        poly_id, count = _refine_assign_f64(
            refine_idx, poly_id, count, px_np, py_np, prep, poly_of_tile)
        refined = len(refine_idx)
    # map dense kernel ranks back to the caller's original polygon ids
    out_ids = np.full(n, -1, np.int64)
    valid_a = poly_id >= 0
    out_ids[valid_a] = poly_uids[poly_id[valid_a]]
    return out_ids, count, {
        "pairs": int(len(pl_.pair_pt)), "refined": refined,
        "host_rows": len(host_rows),
        "flagged": int((band > 0).sum()),
    }


def _poly_of_tile_from(prep: "LayerPrep", poly_of_edge):
    """(rank_of_tile [n_etiles], unique_ids [P]): per-edge-tile polygon
    RANKS (dense 0..P-1 — the i32 kernel encoding and every internal
    group key use ranks, so sparse/large ids neither overflow nor size
    arrays) plus the rank -> original-id mapping for outputs."""
    pids, counts, _ = _group_ids(np.asarray(poly_of_edge, np.int64))
    tiles_per = -(-counts // EDGE_TILE)
    return np.repeat(np.arange(len(pids)), tiles_per), pids


def pip_layer_join(
    px_np: np.ndarray,
    py_np: np.ndarray,
    x1: np.ndarray,
    y1: np.ndarray,
    x2: np.ndarray,
    y2: np.ndarray,
    poly_of_edge: np.ndarray,
    eps: float = 1e-4,
    interpret: bool = False,
    prep: "LayerPrep | None" = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Full spatial-join pair emission: returns (point_rows [M],
    polygon_ids [M]) — one row per (point, containing polygon) pair,
    INCLUDING multiplicity for overlapping layers (points contained in
    k polygons emit k pairs, enumerated exactly on the host from the
    pair list's candidates). The SQL engine's ON st_contains path."""
    if prep is None:
        prep = prepare_layer(px_np, py_np, x1, y1, x2, y2, poly_of_edge)
    groups = _poly_of_tile_from(prep, poly_of_edge)
    poly_id, count, _info = pip_layer_assign(
        px_np, py_np, x1, y1, x2, y2, poly_of_edge,
        eps=eps, interpret=interpret, prep=prep,
        poly_of_tile=groups,
    )
    single = np.nonzero(count == 1)[0]
    pt_rows = [single]
    polys = [poly_id[single].astype(np.int64)]
    multi = np.nonzero(count > 1)[0]
    if len(multi):
        mp, mrank = _multi_assign_f64(multi, px_np, py_np, prep,
                                      groups[0])
        pt_rows.append(mp)
        polys.append(groups[1][mrank])  # ranks -> original ids
    return np.concatenate(pt_rows), np.concatenate(polys)


def _multi_assign_f64(idx, px_np, py_np, prep, poly_of_tile):
    """Exact f64 enumeration of EVERY containing polygon for the given
    points (the overlap path of pip_layer_join)."""
    pl_ = prep.pairs
    ex1, ey1, ex2, ey2 = prep.ex1, prep.ey1, prep.ex2, prep.ey2
    csr_tiles, csr_starts = _tile_pair_csr(pl_)
    out_pt = []
    out_poly = []
    by_tile: dict = {}
    for i in idx:
        by_tile.setdefault(i // POINT_TILE, []).append(i)
    for ptid, pts in by_tile.items():
        ets = _ets_of_tile(pl_, csr_tiles, csr_starts, int(ptid))
        if not len(ets):
            continue
        pids = poly_of_tile[ets]
        ii = np.asarray(pts)
        pxi = px_np[ii][:, None]
        pyi = py_np[ii][:, None]
        for pid in np.unique(pids):
            sl = np.concatenate([
                np.arange(e * EDGE_TILE, (e + 1) * EDGE_TILE)
                for e in ets[pids == pid]
            ])
            a1, b1 = ex1[sl], ey1[sl]
            a2, b2 = ex2[sl], ey2[sl]
            condx = (b1[None] <= pyi) != (b2[None] <= pyi)
            tt = (pyi - b1[None]) / np.where(b2 == b1, 1.0, b2 - b1)[None]
            xc = a1[None] + tt * (a2 - a1)[None]
            inside = (np.sum(condx & (xc > pxi), 1) % 2) == 1
            hit = ii[inside]
            out_pt.append(hit)
            out_poly.append(np.full(len(hit), pid, np.int64))
    if not out_pt:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    return np.concatenate(out_pt), np.concatenate(out_poly)


def _refine_assign_f64(idx, poly_id, count, px_np, py_np, prep,
                       poly_of_tile):
    """Exact f64 per-polygon parity for the given point indices, over the
    pair list's candidate polygons of each point's tile."""
    pl_ = prep.pairs
    ex1, ey1, ex2, ey2 = prep.ex1, prep.ey1, prep.ex2, prep.ey2
    csr_tiles, csr_starts = _tile_pair_csr(pl_)
    by_tile: dict = {}
    for i in idx:
        by_tile.setdefault(i // POINT_TILE, []).append(i)
    poly_id = poly_id.copy()
    count = count.copy()
    for ptid, pts in by_tile.items():
        ets = _ets_of_tile(pl_, csr_tiles, csr_starts, int(ptid))
        ii = np.asarray(pts)
        if not len(ets):
            poly_id[ii] = -1
            count[ii] = 0
            continue
        pids = poly_of_tile[ets]
        pxi = px_np[ii][:, None]
        pyi = py_np[ii][:, None]
        acc_id = np.full(len(ii), -1, np.int64)
        acc_n = np.zeros(len(ii), np.int64)
        for pid in np.unique(pids):
            sl = np.concatenate([
                np.arange(e * EDGE_TILE, (e + 1) * EDGE_TILE)
                for e in ets[pids == pid]
            ])
            a1, b1 = ex1[sl], ey1[sl]
            a2, b2 = ex2[sl], ey2[sl]
            condx = (b1[None] <= pyi) != (b2[None] <= pyi)
            tt = (pyi - b1[None]) / np.where(b2 == b1, 1.0, b2 - b1)[None]
            xc = a1[None] + tt * (a2 - a1)[None]
            inside = (np.sum(condx & (xc > pxi), 1) % 2) == 1
            acc_id = np.where(inside, pid, acc_id)
            acc_n += inside
        poly_id[ii] = np.where(acc_n == 1, acc_id, -1)
        count[ii] = acc_n
    return poly_id, count


@functools.partial(
    jax.jit, static_argnames=("n_ptiles", "n_etiles", "eps", "interpret")
)
def _pip_sparse_call(
    px, py, x1, y1, x2, y2, pair_pt, pair_et,
    n_ptiles: int, n_etiles: int, eps: float, interpret: bool,
):
    """One pallas invocation over one (pow2-padded) pair chunk. The out
    array carries ONE EXTRA scratch tile (index n_ptiles) that padding
    pairs target, so real tiles are never corrupted."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dt = jnp.float32
    # one extra SCRATCH point tile (index n_ptiles): capacity-padding
    # pairs target it for both input fetch AND output, so padded programs
    # never address out-of-bounds blocks (round-3 review finding)
    pxp = jnp.concatenate(
        [px.astype(dt), jnp.full(POINT_TILE, 1e8, dt)]
    ).reshape(-1, 1, POINT_TILE)
    pyp = jnp.concatenate(
        [py.astype(dt), jnp.full(POINT_TILE, 1e8, dt)]
    ).reshape(-1, 1, POINT_TILE)
    e1 = x1.astype(dt).reshape(-1, 1, EDGE_TILE)
    f1 = y1.astype(dt).reshape(-1, 1, EDGE_TILE)
    e2 = x2.astype(dt).reshape(-1, 1, EDGE_TILE)
    f2 = y2.astype(dt).reshape(-1, 1, EDGE_TILE)
    M = pair_pt.shape[0]

    point_block = pl.BlockSpec(
        (1, 1, POINT_TILE), lambda m, pt, et: (pt[m], 0, 0)
    )
    edge_block = pl.BlockSpec(
        (1, 1, EDGE_TILE), lambda m, pt, et: (et[m], 0, 0)
    )
    out_block = pl.BlockSpec(
        (1, 1, POINT_TILE), lambda m, pt, et: (pt[m], 0, 0)
    )
    out_shape = jax.ShapeDtypeStruct(
        (n_ptiles + 1, 1, POINT_TILE), jnp.int32
    )

    with _enable_x64(False):
        counts = pl.pallas_call(
            _sparse_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(M,),
                in_specs=[point_block, point_block,
                          edge_block, edge_block, edge_block, edge_block],
                out_specs=out_block,
            ),
            out_shape=out_shape,
            interpret=interpret,
        )(pair_pt, pair_et, pxp, pyp, e1, f1, e2, f2)
        band = pl.pallas_call(
            functools.partial(_sparse_band_kernel, eps=eps),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(M,),
                in_specs=[point_block, point_block,
                          edge_block, edge_block, edge_block, edge_block],
                out_specs=out_block,
            ),
            out_shape=out_shape,
            interpret=interpret,
        )(pair_pt, pair_et, pxp, pyp, e1, f1, e2, f2)
    return counts, band


# at ~8 B of SMEM per pair (two i32 scalars), the TPU's ~1 MB scalar-
# prefetch budget caps a single call near 128k pairs; chunks split at
# point-tile boundaries so every tile's accumulation stays in one call
MAX_PAIRS_PER_CALL = 1 << 16


def chunk_pairs(pair_pt, pair_et, cap=MAX_PAIRS_PER_CALL):
    """Split the (pt-sorted) pair list into chunks of <= cap pairs,
    PREFERRING tile boundaries. A single tile denser than cap is split
    mid-tile — the caller ACCUMULATES (+=) rather than assigns for tiles
    it has already seen, and the kernel's first-visit zeroing only fires
    on each chunk's first pair of a tile, so partial counts add exactly
    (crossing counts and band flags are both additive)."""
    M = len(pair_pt)
    chunks = []
    start = 0
    while start < M:
        end = min(start + cap, M)
        if end < M:
            # back off to the last tile boundary if one exists
            back = end
            while back > start and pair_pt[back] == pair_pt[back - 1]:
                back -= 1
            if back > start:
                end = back
        chunks.append((start, end))
        start = end
    return chunks


def pip_layer_sparse(
    px: jax.Array,          # [n_ptiles * POINT_TILE] padded, tile-ordered
    py: jax.Array,
    x1: jax.Array,          # [n_etiles * EDGE_TILE] polygon-padded
    y1: jax.Array,
    x2: jax.Array,
    y2: jax.Array,
    pair_pt,                # [M] int32, sorted by point tile
    pair_et,                # [M] int32
    n_ptiles: int = 0,
    n_etiles: int = 0,
    eps: float = 1e-4,
    interpret: bool = False,
    max_pairs_per_call: int = MAX_PAIRS_PER_CALL,
) -> Tuple[jax.Array, jax.Array]:
    """Sparse-pair crossing counts + boundary-band flags.

    Returns (counts int32 [n_ptiles*POINT_TILE], band int32 same shape).
    Tiles never named in pair_pt hold GARBAGE — mask with PairList.covered
    (they are provably outside every polygon bbox => count 0, band 0).
    Internally chunked: each pallas call takes <= MAX_PAIRS_PER_CALL
    pairs (SMEM scalar-prefetch budget), split at tile boundaries."""
    from geomesa_tpu.utils.padding import next_pow2

    pt_np = np.asarray(pair_pt, np.int32)
    et_np = np.asarray(pair_et, np.int32)
    out_c = np.zeros((n_ptiles, POINT_TILE), np.int32)
    out_b = np.zeros((n_ptiles, POINT_TILE), np.int32)
    seen: set = set()
    for s0, s1 in chunk_pairs(pt_np, et_np, cap=max_pairs_per_call):
        seg_pt = pt_np[s0:s1]
        seg_et = et_np[s0:s1]
        cap = max(next_pow2(len(seg_pt)), 256)
        pad = cap - len(seg_pt)
        if pad:
            seg_pt = np.concatenate(
                [seg_pt, np.full(pad, n_ptiles, np.int32)])
            seg_et = np.concatenate([seg_et, np.zeros(pad, np.int32)])
        counts, band = _pip_sparse_call(
            px, py, x1, y1, x2, y2,
            jnp.asarray(seg_pt), jnp.asarray(seg_et),
            n_ptiles=n_ptiles, n_etiles=n_etiles, eps=eps,
            interpret=interpret,
        )
        cc = np.asarray(counts).reshape(n_ptiles + 1, POINT_TILE)
        bb = np.asarray(band).reshape(n_ptiles + 1, POINT_TILE)
        for t in np.unique(pt_np[s0:s1]):
            if t in seen:  # tile split across chunks: partials ADD
                out_c[t] += cc[t]
                out_b[t] += bb[t]
            else:
                out_c[t] = cc[t]
                out_b[t] = bb[t]
                seen.add(int(t))
    return out_c.reshape(-1), out_b.reshape(-1)


def _tile_pair_csr(pl_: "PairList"):
    """CSR view of the (pt-sorted) pair list: (tiles [K], starts [K+1])
    so tile tiles[i]'s edge tiles are pair_et[starts[i]:starts[i+1]].
    O(K) from the precomputed `first` markers — the refine paths used to
    rebuild a python dict by looping the ENTIRE pair list (round-4
    review: seconds of host time at config-2 scale)."""
    pt = np.asarray(pl_.pair_pt, np.int64)
    s = np.nonzero(np.asarray(pl_.first))[0]
    return pt[s], np.concatenate([s, [len(pt)]])


def _ets_of_tile(pl_, tiles, starts, ptid: int) -> np.ndarray:
    k = int(np.searchsorted(tiles, ptid))
    if k >= len(tiles) or tiles[k] != ptid:
        return np.zeros(0, np.int64)
    return np.asarray(pl_.pair_et[starts[k]: starts[k + 1]], np.int64)


class LayerPrep(NamedTuple):
    """Everything the sparse kernels need, host-built once per layer
    (the prepared-geometry/index analog)."""

    pxp: np.ndarray
    pyp: np.ndarray
    ex1: np.ndarray
    ey1: np.ndarray
    ex2: np.ndarray
    ey2: np.ndarray
    pairs: PairList
    n_ptiles: int
    n_etiles: int


def prepare_layer(
    px_np, py_np, x1, y1, x2, y2, poly_of_edge, margin: float = 1e-3
) -> LayerPrep:
    """Z-tile the points, polygon-pad the edges, bbox-prune pairs."""
    n = len(px_np)
    npad = (-n) % POINT_TILE
    pxp = np.concatenate([px_np, np.full(npad, 1e8)])
    pyp = np.concatenate([py_np, np.full(npad, 1e8)])
    n_ptiles = len(pxp) // POINT_TILE
    tx = pxp.reshape(n_ptiles, POINT_TILE)
    ty = pyp.reshape(n_ptiles, POINT_TILE)
    ptile_bbox = np.stack(
        [tx.min(1), ty.min(1), tx.max(1), ty.max(1)], 1
    )
    # padded tail tile bbox is at 1e8: never intersects a polygon

    ex1, ey1, ex2, ey2, poly_of_tile = pad_polygon_edges(
        x1, y1, x2, y2, poly_of_edge
    )
    n_etiles = len(ex1) // EDGE_TILE
    tiles = lambda a: a.reshape(n_etiles, EDGE_TILE)  # noqa: E731
    real = tiles(ey1) < BIG / 2  # degenerate edges excluded from bboxes

    def _bb(a, lo):
        v = np.where(real, tiles(a), np.inf if lo else -np.inf)
        return v.min(1) if lo else v.max(1)

    etile_bbox = np.stack([
        _bb(np.minimum(ex1, ex2), True), _bb(np.minimum(ey1, ey2), True),
        _bb(np.maximum(ex1, ex2), False), _bb(np.maximum(ey1, ey2), False),
    ], 1)
    # per-polygon bboxes via reduceat over pid-sorted edges (the naive
    # per-polygon masking re-scanned the edge table 10k times). Both the
    # bbox table and build_pairs work in DENSE RANK space (0..P-1), so
    # sparse/large polygon ids never size an array (round-4 review)
    poe = np.asarray(poly_of_edge, np.int64)
    pids, counts, order = _group_ids(poe)
    bounds = np.concatenate([[0], np.cumsum(counts)[:-1]])
    exmin = np.minimum(x1, x2)[order]
    eymin = np.minimum(y1, y2)[order]
    exmax = np.maximum(x1, x2)[order]
    eymax = np.maximum(y1, y2)[order]
    poly_bbox = np.stack([
        np.minimum.reduceat(exmin, bounds),
        np.minimum.reduceat(eymin, bounds),
        np.maximum.reduceat(exmax, bounds),
        np.maximum.reduceat(eymax, bounds),
    ], 1)
    pot_rank = np.searchsorted(pids, poly_of_tile)
    pairs = build_pairs(
        ptile_bbox, etile_bbox, pot_rank, poly_bbox, margin=margin
    )
    return LayerPrep(pxp, pyp, ex1, ey1, ex2, ey2, pairs,
                     n_ptiles, n_etiles)


def _refine_band_f64(px_np, py_np, ex1, ey1, ex2, ey2, pl_, inside, flagged):
    """Exact f64 re-evaluation of band-flagged points over the SAME pair
    candidate set, vectorized per point tile ([pts-in-tile, E] ops).
    Mutates `inside` in place; returns the refined count. Shared by the
    single-device and mesh-sharded drivers."""
    refined = 0
    csr_tiles, csr_starts = _tile_pair_csr(pl_)
    by_tile: dict = {}
    for i in flagged:
        by_tile.setdefault(i // POINT_TILE, []).append(i)
    for ptid, idxs in by_tile.items():
        ets = _ets_of_tile(pl_, csr_tiles, csr_starts, ptid)
        ii = np.asarray(idxs)
        if not len(ets):
            inside[ii] = False
            continue
        sl = np.concatenate(
            [np.arange(e * EDGE_TILE, (e + 1) * EDGE_TILE) for e in ets]
        )
        a1, b1 = ex1[sl], ey1[sl]
        a2, b2 = ex2[sl], ey2[sl]
        pxi = px_np[ii][:, None]
        pyi = py_np[ii][:, None]
        condx = (b1[None, :] <= pyi) != (b2[None, :] <= pyi)
        tt = (pyi - b1[None, :]) / np.where(b2 == b1, 1.0, b2 - b1)[None, :]
        xc = a1[None, :] + tt * (a2 - a1)[None, :]
        inside[ii] = (np.sum(condx & (xc > pxi), axis=1) % 2) == 1
        refined += len(ii)
    return refined


# --- LayerPrep persistence (round 5, VERDICT r4 task 5) ---------------------
# The pair list is (point-batch x layer)-intrinsic state, exactly like the
# reference's prepared-geometry cache (SURVEY.md:184-186): content-addressed
# on the input arrays, persisted as one .npz, with a small in-process LRU in
# front. At the 10k-polygon config-2 shape the host build costs ~5 s; a
# cache hit loads in ~0.1 s, so the FIRST query of a new process stops being
# host-bound.

_PREP_MEM_CACHE: "dict[str, LayerPrep]" = {}
_PREP_MEM_MAX = 4
# bytes cap so one-shot joins over big batches cannot pin multi-GB padded
# copies for the process lifetime (review finding); the entry just built
# is always admitted — eviction only sheds OLDER entries
_PREP_MEM_MAX_BYTES = 512 << 20
# created eagerly at import: the old lazy `if _PREP_LOCK is None:
# _PREP_LOCK = Lock()` double-check was itself the race it guarded
# against — two warm-up threads could mint two locks (GT12)
_PREP_LOCK = threading.Lock()


def _prep_lock():
    return _PREP_LOCK


def _prep_nbytes(prep: LayerPrep) -> int:
    return sum(a.nbytes for a in prep[:6]) + sum(
        a.nbytes for a in prep.pairs[:4])


def _prep_cache_put(key: str, prep: LayerPrep) -> None:
    with _prep_lock():
        _PREP_MEM_CACHE[key] = prep
        while len(_PREP_MEM_CACHE) > 1 and (
            len(_PREP_MEM_CACHE) > _PREP_MEM_MAX
            or sum(map(_prep_nbytes, _PREP_MEM_CACHE.values()))
            > _PREP_MEM_MAX_BYTES
        ):
            oldest = next(iter(_PREP_MEM_CACHE))
            if oldest == key:  # never evict the entry just inserted
                break
            _PREP_MEM_CACHE.pop(oldest)


def layer_prep_key(px_np, py_np, x1, y1, x2, y2, poly_of_edge,
                   margin: float = 1e-3) -> str:
    """Content fingerprint of (point batch, polygon layer, tiling
    constants). sha1 over the raw bytes: ~100 ms at 4M points — 50x
    cheaper than the build it saves."""
    import hashlib

    h = hashlib.sha1()
    for a in (px_np, py_np, x1, y1, x2, y2, poly_of_edge):
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    h.update(f"m{margin};pt{POINT_TILE};et{EDGE_TILE};v1".encode())
    return h.hexdigest()


def save_layer_prep(prep: LayerPrep, path: str) -> None:
    import os

    tmp = path + f".tmp{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            np.savez(
                f,
                pxp=prep.pxp, pyp=prep.pyp,
                ex1=prep.ex1, ey1=prep.ey1, ex2=prep.ex2, ey2=prep.ey2,
                pair_pt=prep.pairs.pair_pt, pair_et=prep.pairs.pair_et,
                first=prep.pairs.first, covered=prep.pairs.covered,
                scalars=np.asarray(
                    [prep.n_ptiles, prep.n_etiles,
                     prep.pairs.n_ptiles, prep.pairs.n_etiles], np.int64),
            )
        os.replace(tmp, path)
    except BaseException:
        # never leave a partial multi-hundred-MB tmp behind (ENOSPC would
        # otherwise worsen the very pressure that caused the failure)
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise


def load_layer_prep(path: str) -> LayerPrep:
    with np.load(path, allow_pickle=False) as z:
        sc = z["scalars"]
        return LayerPrep(
            z["pxp"], z["pyp"], z["ex1"], z["ey1"], z["ex2"], z["ey2"],
            PairList(z["pair_pt"], z["pair_et"], z["first"], z["covered"],
                     int(sc[2]), int(sc[3])),
            int(sc[0]), int(sc[1]),
        )


def prepare_layer_cached(
    px_np, py_np, x1, y1, x2, y2, poly_of_edge,
    margin: float = 1e-3, cache_dir: "str | None" = None,
    key: "str | None" = None,
) -> LayerPrep:
    """prepare_layer behind a content-addressed cache: in-process LRU
    first, then `cache_dir` (or the geomesa.spatial.prep.cache.dir system
    property; empty = memory only) on disk. A corrupt/unreadable disk
    entry falls through to a rebuild. `key` may carry a precomputed
    layer_prep_key to skip re-hashing the inputs."""
    import os

    from geomesa_tpu.utils.config import SystemProperties

    if key is None:
        key = layer_prep_key(
            px_np, py_np, x1, y1, x2, y2, poly_of_edge, margin)
    with _prep_lock():
        hit = _PREP_MEM_CACHE.get(key)
        if hit is not None:
            # true LRU: refresh recency (eviction pops insertion order)
            _PREP_MEM_CACHE.pop(key)
            _PREP_MEM_CACHE[key] = hit
    if hit is not None:
        return hit
    if cache_dir is None:
        cache_dir = str(SystemProperties.SPATIAL_PREP_CACHE_DIR.get()) or None
    path = os.path.join(cache_dir, f"layerprep_{key}.npz") if cache_dir else None
    prep = None
    if path and os.path.exists(path):
        try:
            prep = load_layer_prep(path)
        except Exception:
            prep = None
    if prep is None:
        prep = prepare_layer(px_np, py_np, x1, y1, x2, y2, poly_of_edge,
                             margin=margin)
        if path:
            try:
                os.makedirs(cache_dir, exist_ok=True)
                save_layer_prep(prep, path)
            except OSError:
                pass
    _prep_cache_put(key, prep)
    return prep


def pip_layer(
    px_np: np.ndarray,
    py_np: np.ndarray,
    x1: np.ndarray,
    y1: np.ndarray,
    x2: np.ndarray,
    y2: np.ndarray,
    poly_of_edge: np.ndarray,
    eps: float = 1e-4,
    interpret: bool = False,
    refine_f64: bool = True,
    prep: "LayerPrep | None" = None,
    points_device=None,
):
    """End-to-end host orchestration: prepare_layer + sparse kernels +
    f64 band refinement.

    Returns (inside bool [N], info dict). Points are assumed Z/store-
    ordered (tile bboxes are only tight then); correctness holds for any
    order. `points_device` optionally supplies the PADDED point arrays
    already device-resident (uploaded concurrently with an async prep
    build — the overlap path); the host refine still reads px_np/py_np."""
    n = len(px_np)
    if prep is None:
        prep = prepare_layer(px_np, py_np, x1, y1, x2, y2, poly_of_edge)
    pxp, pyp = prep.pxp, prep.pyp
    ex1, ey1, ex2, ey2 = prep.ex1, prep.ey1, prep.ex2, prep.ey2
    n_ptiles, n_etiles = prep.n_ptiles, prep.n_etiles
    pl_ = prep.pairs

    if len(pl_.pair_pt) == 0:
        # same info keys as the normal return: callers index 'flagged'
        # and 'refine_s' unconditionally
        return np.zeros(n, bool), {"pairs": 0, "refined": 0,
                                   "n_ptiles": n_ptiles,
                                   "n_etiles": n_etiles,
                                   "flagged": 0, "refine_s": 0.0}

    if points_device is not None:
        pxp, pyp = points_device  # padded, already device-resident
    counts, band = pip_layer_grouped(
        pxp, pyp,
        jnp.asarray(ex1), jnp.asarray(ey1),
        jnp.asarray(ex2), jnp.asarray(ey2),
        pl_.pair_pt, pl_.pair_et,
        n_ptiles=n_ptiles, n_etiles=n_etiles, eps=eps,
        interpret=interpret,
    )
    counts = np.array(counts).reshape(n_ptiles, POINT_TILE)
    band_np = np.array(band).reshape(n_ptiles, POINT_TILE)
    counts[~pl_.covered] = 0
    band_np[~pl_.covered] = 0
    inside = (counts.reshape(-1)[:n] % 2) == 1
    flagged = np.nonzero(band_np.reshape(-1)[:n] > 0)[0]

    refined = 0
    refine_s = 0.0
    if refine_f64 and len(flagged):
        import time as _time

        _t0 = _time.perf_counter()
        refined = _refine_band_f64(
            px_np, py_np, ex1, ey1, ex2, ey2, pl_, inside, flagged)
        refine_s = _time.perf_counter() - _t0
    return inside, {
        "pairs": int(len(pl_.pair_pt)), "refined": refined,
        "n_ptiles": n_ptiles, "n_etiles": n_etiles,
        "flagged": int(len(flagged)), "refine_s": round(refine_s, 3),
    }


def pip_layer_sharded(
    mesh,
    px_np: np.ndarray,
    py_np: np.ndarray,
    x1: np.ndarray,
    y1: np.ndarray,
    x2: np.ndarray,
    y2: np.ndarray,
    poly_of_edge: np.ndarray,
    eps: float = 1e-4,
    interpret: bool = False,
    refine_f64: bool = True,
):
    """Config-2 spatial join over a device mesh (round 5, VERDICT task 4).

    Point tiles are sharded across the mesh; the padded edge table rides
    REPLICATED (polygon layers are MBs against GB point sets — the same
    asymmetry the reference exploits by broadcasting the small join side).
    One shard_map Pallas pass at a single global capacity class (pow2 of
    the max per-tile pair count; the single-chip driver's per-tile
    bucketing matters for 10k-polygon skew, not at mesh-dryrun shapes),
    then the SAME host-side parity finish + f64 band refinement as
    pip_layer. Returns (inside bool [N], info dict)."""
    from jax.sharding import PartitionSpec as P

    from geomesa_tpu.parallel.mesh import SHARD_AXIS
    from jax import shard_map

    n = len(px_np)
    prep = prepare_layer(px_np, py_np, x1, y1, x2, y2, poly_of_edge)
    pl_ = prep.pairs
    ex1, ey1, ex2, ey2 = prep.ex1, prep.ey1, prep.ex2, prep.ey2
    n_etiles = prep.n_etiles
    if len(pl_.pair_pt) == 0:
        # same info keys as the normal return below
        return np.zeros(n, bool), {
            "pairs": 0, "refined": 0, "n_ptiles": prep.n_ptiles,
            "n_etiles": n_etiles, "flagged": 0, "cap": 0,
            "shards": int(np.prod(mesh.devices.shape)),
        }

    D = int(np.prod(mesh.devices.shape))
    nt = prep.n_ptiles
    tpd = -(-nt // D)
    ntp = tpd * D

    pt_np = np.asarray(pl_.pair_pt, np.int64)
    et_np = np.asarray(pl_.pair_et, np.int64)
    counts_t = np.bincount(pt_np, minlength=ntp)
    cap = int(_pow2_caps(np.asarray([counts_t.max()]))[0])
    if cap > MAX_ETAB_SLOTS:
        raise ValueError(
            f"per-tile pair count {counts_t.max()} exceeds the SMEM etab "
            f"budget ({MAX_ETAB_SLOTS}); shard a smaller layer or use the "
            "single-chip pip_layer driver (it chunks by column)"
        )
    etab = np.full((ntp, cap), n_etiles, np.int32)
    order = np.argsort(pt_np, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts_t)[:-1]])
    col = np.arange(len(order)) - starts[pt_np[order]]
    etab[pt_np[order], col] = et_np[order]

    pad_pts = ntp * POINT_TILE - len(prep.pxp)
    pxp = np.concatenate([prep.pxp, np.full(pad_pts, 1e8)])
    pyp = np.concatenate([prep.pyp, np.full(pad_pts, 1e8)])

    dt32 = jnp.float32
    ax1 = jnp.concatenate([jnp.asarray(ex1, dt32), jnp.zeros(EDGE_TILE, dt32)])
    ay1 = jnp.concatenate([jnp.asarray(ey1, dt32),
                           jnp.full(EDGE_TILE, BIG, dt32)])
    ax2 = jnp.concatenate([jnp.asarray(ex2, dt32), jnp.zeros(EDGE_TILE, dt32)])
    ay2 = jnp.concatenate([jnp.asarray(ey2, dt32),
                           jnp.full(EDGE_TILE, BIG, dt32)])

    def shard_fn(pxl, pyl, etabl, a1, b1, a2, b2):
        return _pip_grouped_call(
            pxl.reshape(tpd, POINT_TILE), pyl.reshape(tpd, POINT_TILE),
            a1, b1, a2, b2, etabl,
            cap=cap, n_etiles=n_etiles, eps=eps, interpret=interpret,
        )

    f = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                  P(), P(), P(), P()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        check_vma=False,  # pallas outputs carry no vma (knn_scan idiom)
    )
    counts, band = f(
        jnp.asarray(pxp, dt32), jnp.asarray(pyp, dt32), jnp.asarray(etab),
        ax1, ay1, ax2, ay2,
    )

    counts = np.array(counts).reshape(ntp, POINT_TILE)[:nt]
    band_np = np.array(band).reshape(ntp, POINT_TILE)[:nt]
    counts[~pl_.covered] = 0
    band_np[~pl_.covered] = 0
    inside = (counts.reshape(-1)[:n] % 2) == 1
    flagged = np.nonzero(band_np.reshape(-1)[:n] > 0)[0]
    refined = 0
    if refine_f64 and len(flagged):
        refined = _refine_band_f64(
            px_np, py_np, ex1, ey1, ex2, ey2, pl_, inside, flagged)
    return inside, {
        "pairs": int(len(pt_np)), "refined": refined,
        "n_ptiles": nt, "n_etiles": n_etiles,
        "flagged": int(len(flagged)), "cap": cap, "shards": D,
    }
