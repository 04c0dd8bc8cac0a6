"""Device-side grid index: O(N) build, per-query candidate pruning for kNN.

Parity role: the reference's KNN process avoids scanning the world by
windowed index queries (KNearestNeighborSearchProcess's estimated-radius
window + geometric expansion — SURVEY.md §3.4); its exactness comes from
re-querying until the window provably contains the true neighbors. This is
the TPU-native equivalent: a batch-resident spatial index built ON DEVICE
(one sort), then per-query candidate gathering from a fixed cell
neighborhood with a per-query EXACTNESS CERTIFICATE, and a fallback scan
for the (rare) queries the certificate cannot prove.

Index build (O(N log N) sort, amortized over all queries against a batch):
  cell(p) = (floor((lon+180)/360*G), floor((lat+90)/180*G)) on a G x G
  lon/lat grid; points argsorted by where(mask, cell_id, G*G) so masked
  rows sink to the tail; per-cell [start, end) offsets by searchsorted.

Query (static shapes): each query gathers the (2R+1)^2 cell neighborhood
around its own cell, S candidate slots per cell (cells larger than S set an
overflow flag), computes exact haversine over the gathered candidates, and
takes top-k.

Certificate (sphere-safe): every point OUTSIDE the searched square differs
from the query by >= dlat degrees latitude or >= dlon degrees longitude
(to the square's nearer unsearched edge). Lower bounds on its distance:
  lat:  d >= R * dlat_rad                      (meridian arc)
  lon:  d >= R * asin(sin(dlon_rad) * cos(lat_q))   (distance to the
        meridian great circle every path must cross; valid dlon <= 90deg)
The result is exact iff kth_dist <= min(edge bounds), no gathered cell
overflowed, fewer than k candidates never happened, and no clipped grid
edge hides wraparound neighbors (lon edges; lat edges are true poles).
Flagged queries are re-run by the caller on an exact full-scan path
(`knn`/`knn_mxu`) — the moral equivalent of the reference's window
expansion loop, except the common case needs no second round trip.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from jax import shard_map as _shard_map

from geomesa_tpu.engine.geodesy import EARTH_RADIUS_M, haversine_m
from geomesa_tpu.utils.padding import next_pow2

INF = jnp.float32(jnp.inf)


def auto_grid_params(match_count: int,
                     per_cell_target: int = 16) -> Tuple[int, int]:
    """(g, cell_slots) sized to the matched-point count: grid edge chosen
    so the GLOBAL-mean per-cell occupancy is ~per_cell_target, with slot
    capacity 16x that — geo workloads concentrate matches (a predicate
    bbox covering ~1/10 of the grid means dense-region occupancy ~10x the
    global mean), and slots must absorb that skew or dense cells overflow
    and every query near them pays the exact fallback on top of the wasted
    sort. (Correctness never depends on these numbers — overflow only
    flags queries for fallback.)

    Too-coarse grids overflow everywhere; too-fine grids make the
    (2R+1)^2 neighborhood too sparse to hold k candidates (the 'short'
    flag forces fallback). Both degenerate silently to full scans, so
    sizing matters for speed. Calibrated on TPU v5e at 67M points / 3.1M
    matches in a 120x50deg window: g=512, slots=256 certifies all queries.
    """
    import math

    g = 1 << max(
        6, min(11, int(math.sqrt(max(match_count, 1) / per_cell_target)
                       ).bit_length())
    )
    return g, 16 * per_cell_target


class GridIndex(NamedTuple):
    """Batch-resident spatial index (all device arrays)."""

    sx: jax.Array       # [N] lon, sorted by cell
    sy: jax.Array       # [N] lat, sorted by cell
    sidx: jax.Array     # [N] original row of each sorted point (int32)
    starts: jax.Array   # [G*G + 1] cell -> first sorted row
    counts: jax.Array   # [G*G] matched points per cell
    g: int              # grid edge (static)


@functools.partial(jax.jit, static_argnames=("g",))
def build_grid_index(x: jax.Array, y: jax.Array, mask: jax.Array,
                     g: int = 128) -> GridIndex:
    """Sort the batch by grid cell (masked rows last). One device sort +
    three gathers; reusable across every query against this batch."""
    n = x.shape[0]
    cx = jnp.clip(jnp.floor((x + 180.0) / 360.0 * g).astype(jnp.int32), 0, g - 1)
    cy = jnp.clip(jnp.floor((y + 90.0) / 180.0 * g).astype(jnp.int32), 0, g - 1)
    cell = cy * g + cx
    key = jnp.where(mask, cell, g * g)  # masked -> sentinel tail bucket
    # variadic sort carries the payload columns through the sort network:
    # argsort + three post-hoc random gathers measured ~13x slower on TPU
    # (random 67M-element gathers dominate; the sort itself is ~0.4s)
    skey, sx, sy, sidx = jax.lax.sort(
        (key, x, y, jnp.arange(n, dtype=jnp.int32)), num_keys=1
    )
    starts = jnp.searchsorted(skey, jnp.arange(g * g + 1, dtype=jnp.int32))
    counts = jnp.diff(starts)
    return GridIndex(
        sx=sx,
        sy=sy,
        sidx=sidx,
        starts=starts.astype(jnp.int32),
        counts=counts.astype(jnp.int32),
        g=g,
    )


@functools.partial(
    jax.jit, static_argnames=("k", "ring_radius", "cell_slots")
)
def knn_grid(
    qx: jax.Array,
    qy: jax.Array,
    index: GridIndex,
    k: int,
    ring_radius: int = 2,
    cell_slots: int = 256,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Exact-or-flagged kNN from the grid index.

    Returns (dists [Q,k], original indices [Q,k], uncertain [Q] bool).
    `uncertain=True` means the certificate could not PROVE exactness
    (k-th neighbor too far for the searched square, an overflowing cell in
    range, or a clipped lon edge) — the caller re-runs those queries on a
    full-scan path. Distances/indices for uncertain queries are still the
    best found among gathered candidates.
    """
    gq = index.g
    R = ring_radius
    S = cell_slots
    ncell = (2 * R + 1) ** 2

    qcx = jnp.clip(
        jnp.floor((qx + 180.0) / 360.0 * gq).astype(jnp.int32), 0, gq - 1
    )
    qcy = jnp.clip(
        jnp.floor((qy + 90.0) / 180.0 * gq).astype(jnp.int32), 0, gq - 1
    )

    offs = jnp.arange(-R, R + 1, dtype=jnp.int32)
    ox = jnp.tile(offs, 2 * R + 1)                      # [ncell]
    oy = jnp.repeat(offs, 2 * R + 1)                    # [ncell]

    def one_query(cqx, cqy, qlon, qlat):
        ccx = cqx + ox
        ccy = cqy + oy
        inside = (ccx >= 0) & (ccx < gq) & (ccy >= 0) & (ccy < gq)
        cells = jnp.where(inside, ccy * gq + ccx, 0)
        base = jnp.take(index.starts, cells)            # [ncell]
        cnt = jnp.where(inside, jnp.take(index.counts, cells), 0)
        overflow = jnp.any(cnt > S)
        # lon-edge clipping hides antimeridian neighbors; lat edges are
        # real poles (nothing beyond), so only lon clipping taints
        clipped_lon = jnp.any(((ccx < 0) | (ccx >= gq)))

        lanes = base[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        valid = jnp.arange(S, dtype=jnp.int32)[None, :] < jnp.minimum(cnt, S)[:, None]
        lanes = jnp.clip(lanes.reshape(-1), 0, index.sx.shape[0] - 1)
        px = jnp.take(index.sx, lanes)
        py = jnp.take(index.sy, lanes)
        pidx = jnp.take(index.sidx, lanes)
        d = haversine_m(qlon, qlat, px, py)
        d = jnp.where(valid.reshape(-1), d, INF)
        neg, sel = jax.lax.top_k(-d, k)
        kd = -neg
        ki = jnp.take(pidx, sel)

        # certificate: margins to the square's outer edges, in degrees
        cw = 360.0 / gq
        ch = 180.0 / gq
        west = qlon - (-180.0 + (cqx - R).astype(jnp.float32) * cw)
        east = (-180.0 + (cqx + R + 1).astype(jnp.float32) * cw) - qlon
        south = qlat - (-90.0 + (cqy - R).astype(jnp.float32) * ch)
        north = (-90.0 + (cqy + R + 1).astype(jnp.float32) * ch) - qlat
        deg = jnp.float32(jnp.pi / 180.0)
        lat_bound = jnp.minimum(south, north) * deg * EARTH_RADIUS_M
        dlon = jnp.clip(jnp.minimum(west, east), 0.0, 90.0) * deg
        lon_bound = EARTH_RADIUS_M * jnp.arcsin(
            jnp.sin(dlon) * jnp.cos(qlat * deg)
        )
        d_out = jnp.minimum(lat_bound, lon_bound)
        short = ~jnp.isfinite(kd[k - 1])  # fewer than k candidates gathered
        # f32 safety margin: a rounding-level false "certified" would break
        # exactness silently, so demand a 1m + 1e-6-relative gap
        guard = kd[k - 1] + jnp.maximum(1.0, 1e-6 * kd[k - 1])
        uncertain = (guard > d_out) | overflow | clipped_lon | short
        return kd, ki, uncertain

    return jax.vmap(one_query)(qcx, qcy, qx, qy)


def knn_indexed_sharded(
    mesh,
    qx: jax.Array,
    qy: jax.Array,
    dx: jax.Array,
    dy: jax.Array,
    mask: jax.Array,
    k: int,
    g: int = 128,
    ring_radius: int = 2,
    cell_slots: int = 256,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Grid-index kNN with data sharded over the mesh axis.

    Each device sorts ITS shard into a local grid index (the sort
    parallelizes perfectly — no cross-device data movement), runs the
    certified neighborhood search for the replicated queries, and the
    per-shard top-ks merge by all_gather + re-top-k (C25's reduction-tree
    shape, same argument as knn_sharded: the global top-k is a subset of
    the union of exact per-shard top-ks).

    A query is globally uncertain if ANY shard's certificate failed for it
    (an or-reduce over the gathered flags); callers re-run flagged queries
    on an exact sharded scan (`knn_sharded`). Returns
    (dists [Q,k], global indices [Q,k], uncertain [Q]) replicated.
    """
    import functools

    from jax.sharding import PartitionSpec as P

    from geomesa_tpu.engine.knn import _topk_smallest
    from geomesa_tpu.parallel.mesh import SHARD_AXIS

    d_count = mesh.devices.size
    shard_n = dx.shape[0] // d_count

    @functools.partial(
        _shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=(P(), P(), P()),
        # post-gather merge computes identical values on every device
        check_vma=False,
    )
    def run(qx, qy, dxs, dys, ms):
        index = build_grid_index(dxs, dys, ms, g=g)
        kd, ki, unc = knn_grid(
            qx, qy, index, k=k, ring_radius=ring_radius,
            cell_slots=cell_slots,
        )
        shard = jax.lax.axis_index(SHARD_AXIS)
        gi = ki + shard * shard_n
        all_d = jax.lax.all_gather(kd, SHARD_AXIS)   # [D, Q, k]
        all_i = jax.lax.all_gather(gi, SHARD_AXIS)
        all_u = jax.lax.all_gather(unc, SHARD_AXIS)  # [D, Q]
        pool_d = jnp.moveaxis(all_d, 0, 1).reshape(kd.shape[0], -1)
        pool_i = jnp.moveaxis(all_i, 0, 1).reshape(kd.shape[0], -1)
        md, sel = _topk_smallest(pool_d, k)
        return md, jnp.take_along_axis(pool_i, sel, axis=1), jnp.any(all_u, 0)

    return run(qx, qy, dx, dy, mask)


def knn_indexed(
    qx, qy, dx, dy, mask, k: int,
    g: int = 128, ring_radius: int = 2, cell_slots: int = 256,
    index: GridIndex | None = None,
):
    """Grid-index kNN with exact fallback: certificate-failed queries are
    re-run on the exact full-scan haversine path. Host round trip: one
    bool-vector fetch to decide whether a fallback is needed at all.

    Pass a prebuilt `index` to amortize the build across query rounds
    (the device-cache analog of the reference keeping its index tables).
    """
    import numpy as np

    from geomesa_tpu.engine.knn import knn

    if index is None:
        index = build_grid_index(dx, dy, mask, g=g)
    kd, ki, uncertain = knn_grid(
        qx, qy, index, k=k, ring_radius=ring_radius, cell_slots=cell_slots
    )
    flags = np.asarray(uncertain)
    if not flags.any():
        return kd, ki
    rows = np.nonzero(flags)[0]
    # pow2-bucket the fallback set: the uncertain-query count varies per
    # round, and both the gathered query extent and the tile parameter
    # shape the exact-path executable — raw counts would compile one per
    # distinct count. Padded slots re-run rows[0]; their results are
    # dropped by the slice before the scatter-back.
    nb = next_pow2(max(len(rows), 1))
    rpad = np.concatenate(
        [rows, np.full(nb - len(rows), rows[0], rows.dtype)])
    fd, fi = knn(
        jnp.take(qx, jnp.asarray(rpad)), jnp.take(qy, jnp.asarray(rpad)),
        dx, dy, mask, k=k,
        query_tile=max(1, min(1024, nb)),
    )
    kd = jnp.asarray(kd).at[jnp.asarray(rows)].set(fd[: len(rows)])
    ki = jnp.asarray(ki).at[jnp.asarray(rows)].set(fi[: len(rows)])
    return kd, ki
