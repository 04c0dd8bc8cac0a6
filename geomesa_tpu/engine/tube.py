"""Tube-select kernel: spatio-temporal corridor join.

Parity: geomesa-process TubeSelectProcess (tube/) [upstream, unverified]:
"find features near this track in space AND time". The reference builds tube
segments (buffered geometries + time intervals) host-side via TubeBuilder
variants (NoGapFill / LineGapFill / InterpolatedGapFill) and issues one
spatial+temporal query per segment. TPU-first shape: the tube is a compact
array of (lon, lat, time, radius_m, half_window_ms) samples; the kernel is a
single masked (N data x T tube-samples) haversine + time-window test, tiled
over T — every data point is matched against the whole corridor in one fused
pass instead of S sequential store queries.

Gap-filling lives host-side in process/tube.py (same division of labor as the
reference); this kernel only sees the sampled tube.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from jax import shard_map as _shard_map
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from geomesa_tpu.parallel.mesh import SHARD_AXIS


@functools.partial(jax.jit, static_argnames=("tube_tile", "data_tile"))
def tube_select(
    x: jax.Array,
    y: jax.Array,
    t: jax.Array,
    mask: jax.Array,
    tube_x: jax.Array,
    tube_y: jax.Array,
    tube_t: jax.Array,
    radius_m: jax.Array,
    half_window_ms: jax.Array,
    tube_tile: int = 2048,
    data_tile: int = 8192,
) -> jax.Array:
    """bool [N]: data point matches if within radius AND time window of ANY
    tube sample. Tube arrays are [T]; radius/window may be scalar or [T].

    Tiled over BOTH axes: the [data_tile, tube_tile] hit block is the only
    pairwise intermediate, so HBM stays O(N + T) regardless of problem size
    (a flat [N, T] broadcast at N=4M, T=2k would materialize ~32 GB).

    The pairwise test is a CHORD-SQUARED compare (round 4): d <= r on
    the sphere iff |u_point - u_tube|^2 <= (2 sin(r/2R))^2 — identical
    to the haversine compare in exact arithmetic, but the per-pair work
    is 8 elementwise flops instead of transcendental-heavy haversine
    (per-pair sin/cos/asin on the VPU). The DIFFERENCE form is
    essential: the dot-product form (dot >= cos(r/R)) cancels
    catastrophically in f32 — cos(r/R) rounds to exactly 1.0f below
    r ~ 2.2 km, silently dropping true matches (round-4 review,
    reproduced at 500 m radius); differences of unit-vector components
    keep ~1% relative accuracy at any radius, the same ~1 m floor as
    f32 coordinates themselves. Unit vectors and thresholds are
    precomputed once per point/sample in the INPUT dtype, so f64 inputs
    (the process path, CPU tests) stay f64-exact.
    """
    from geomesa_tpu.engine.geodesy import EARTH_RADIUS_M

    T = tube_x.shape[0]
    n = x.shape[0]
    if T == 0:
        return jnp.zeros((n,), bool)
    radius_m = jnp.broadcast_to(
        jnp.asarray(radius_m, x.dtype), (T,))
    half_window_ms = jnp.broadcast_to(
        jnp.asarray(half_window_ms, jnp.int64), (T,)
    )
    # pad the tube axis only to the lane quantum (128), not a full tile —
    # short tubes (the common case) shouldn't pay 8x padding waste
    tube_tile = min(tube_tile, (T + 127) // 128 * 128)
    tpad = (-T) % tube_tile
    tx = jnp.pad(tube_x, (0, tpad))
    ty = jnp.pad(tube_y, (0, tpad))
    tt = jnp.pad(tube_t, (0, tpad))
    tr = jnp.pad(radius_m, (0, tpad), constant_values=-1.0)
    tw = jnp.pad(half_window_ms, (0, tpad))

    def unit3(lon, lat):
        rlon = jnp.radians(lon)
        rlat = jnp.radians(lat)
        cl = jnp.cos(rlat)
        return jnp.stack(
            [cl * jnp.cos(rlon), cl * jnp.sin(rlon), jnp.sin(rlat)], -1)

    tu = unit3(tx, ty)                      # [Tp, 3]
    # pad samples (r < 0) get threshold -1: chord^2 >= 0 never matches
    half = jnp.sin(tr / (2.0 * EARTH_RADIUS_M))
    thresh = jnp.where(tr < 0, -1.0, 4.0 * half * half)
    tube = (
        tu.reshape(-1, tube_tile, 3),
        thresh.reshape(-1, tube_tile),
        tt.reshape(-1, tube_tile),
        tw.reshape(-1, tube_tile),
    )

    data_tile = min(data_tile, max(n, 1))
    npad = (-n) % data_tile
    xd = jnp.pad(x, (0, npad)).reshape(-1, data_tile)
    yd = jnp.pad(y, (0, npad)).reshape(-1, data_tile)
    td = jnp.pad(t, (0, npad)).reshape(-1, data_tile)

    def data_block(_, args):
        xi, yi, ti = args
        ui = unit3(xi, yi)                  # [data_tile, 3]

        def tube_block(carry, targs):
            tui, thi, tti, twi = targs
            dx = ui[:, None, 0] - tui[None, :, 0]
            dy = ui[:, None, 1] - tui[None, :, 1]
            dz = ui[:, None, 2] - tui[None, :, 2]
            chord_sq = dx * dx + dy * dy + dz * dz
            dt = jnp.abs(ti[:, None] - tti[None, :])
            hit = (chord_sq <= thi[None, :]) & (dt <= twi[None, :])
            return carry | jnp.any(hit, axis=1), None

        init = jnp.zeros_like(xi, dtype=bool)
        out, _ = jax.lax.scan(tube_block, init, tube)
        return None, out

    _, hits = jax.lax.scan(data_block, None, (xd, yd, td))
    return hits.reshape(-1)[:n] & mask


def tube_select_host(x, y, t, tube_x, tube_y, tube_t, radius_m,
                     half_window_ms) -> np.ndarray:
    """f64 host oracle of `tube_select` (no mask): bool [N], a point
    matches when its haversine distance to ANY tube sample is within
    that sample's radius and its time within the sample's half window.
    Loops over the T samples, so memory stays O(N)."""
    from geomesa_tpu.engine.geodesy import haversine_m_np

    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    t = np.asarray(t, np.int64)
    n_t = len(tube_x)
    radius = np.broadcast_to(np.asarray(radius_m, np.float64), (n_t,))
    window = np.broadcast_to(np.asarray(half_window_ms, np.int64), (n_t,))
    hits = np.zeros(len(x), bool)
    for i in range(n_t):
        near = haversine_m_np(x, y, float(tube_x[i]), float(tube_y[i]))
        hits |= (near <= radius[i]) & (
            np.abs(t - np.int64(tube_t[i])) <= window[i])
    return hits


# tube samples per pruning segment: a long track's segment boxes must
# stay LOCAL or the prune is vacuous — at SEG=128 a 256-sample diagonal
# corridor became 2 region-sized boxes covering ~half the data (measured
# round 4: tile_capacity overflowed to ALL tiles, 4.6x; at SEG=16 the
# boxes hug the corridor). The [n_tiles, K] overlap test stays trivial.
SEG = 16


@functools.partial(
    jax.jit, static_argnames=("data_tile", "tile_capacity")
)
def _tube_pruned_call(
    x, y, t, mask,
    tube_x, tube_y, tube_t, radius_m, half_window_ms,
    margin_lon, margin_lat,
    data_tile: int, tile_capacity: int,
):
    n = x.shape[0]
    pad = (-n) % data_tile
    big = 3.0e8  # dtype-preserving: the process path runs f64 coords
    xp = jnp.pad(x, (0, pad), constant_values=big)
    yp = jnp.pad(y, (0, pad), constant_values=big)
    tp = jnp.pad(t, (0, pad))
    mp = jnp.pad(mask, (0, pad))
    nt = xp.shape[0] // data_tile

    # per-data-tile envelopes over ALL rows (filter-independent — the
    # mask still applies inside the kernel; conservative is exact). On
    # store-ordered batches these are tight, which is the whole win.
    xt = xp.reshape(nt, data_tile)
    yt = yp.reshape(nt, data_tile)
    tt_ = tp.reshape(nt, data_tile)
    txmin, txmax = xt.min(1), jnp.where(xt >= big, -big, xt).max(1)
    tymin, tymax = yt.min(1), jnp.where(yt >= big, -big, yt).max(1)
    ttmin, ttmax = tt_.min(1), tt_.max(1)

    # tube segment envelopes ([K] boxes of SEG samples) expanded by the
    # geodesic margins + time window: a long track's global bbox would
    # cover everything; per-segment boxes track the corridor
    T = tube_x.shape[0]
    spad = (-T) % SEG
    sx = jnp.pad(tube_x, (0, spad), constant_values=big)
    sy = jnp.pad(tube_y, (0, spad), constant_values=big)
    st = jnp.pad(tube_t, (0, spad))
    sw = jnp.pad(
        jnp.broadcast_to(jnp.asarray(half_window_ms, jnp.int64), (T,)),
        (0, spad), constant_values=-1,
    )
    K = sx.shape[0] // SEG
    sxs = sx.reshape(K, SEG)
    sys_ = sy.reshape(K, SEG)
    sts = st.reshape(K, SEG)
    sws = sw.reshape(K, SEG)
    live = sxs < big / 2
    inf64 = jnp.int64(1) << 60
    sxmin = jnp.where(live, sxs, big).min(1) - margin_lon
    sxmax = jnp.where(live, sxs, -big).max(1) + margin_lon
    symin = jnp.where(live, sys_, big).min(1) - margin_lat
    symax = jnp.where(live, sys_, -big).max(1) + margin_lat
    wmax = sws.max(1)
    stmin = jnp.where(live, sts, inf64).min(1) - wmax
    stmax = jnp.where(live, sts, -inf64).max(1) + wmax

    # longitude wraps: a corridor reaching past +-180 must also match
    # tiles on the far side, so the x-overlap test additionally checks
    # the +-360-shifted segment boxes (data lons live in [-180, 180];
    # the extra tests are vacuous for interior corridors)
    x_overlap = (
        ((txmax[:, None] >= sxmin[None, :]) & (txmin[:, None] <= sxmax[None, :]))
        | ((txmax[:, None] >= sxmin[None, :] + 360.0)
           & (txmin[:, None] <= sxmax[None, :] + 360.0))
        | ((txmax[:, None] >= sxmin[None, :] - 360.0)
           & (txmin[:, None] <= sxmax[None, :] - 360.0))
    )
    hit = (
        x_overlap
        & (tymax[:, None] >= symin[None, :]) & (tymin[:, None] <= symax[None, :])
        & (ttmax[:, None] >= stmin[None, :]) & (ttmin[:, None] <= stmax[None, :])
    ).any(axis=1)

    n_sel = jnp.sum(hit.astype(jnp.int32))
    cap = min(tile_capacity, nt)
    overflow = n_sel > cap
    picked = jax.lax.top_k(
        jnp.where(hit, -jnp.arange(nt, dtype=jnp.int32), -(1 << 30)), cap
    )[0]
    live_slot = picked > -(1 << 30)
    ids = jnp.where(live_slot, -picked, 0)

    gx = jnp.take(xt, ids, axis=0).reshape(-1)
    gy = jnp.take(yt, ids, axis=0).reshape(-1)
    gt = jnp.take(tt_, ids, axis=0).reshape(-1)
    gm = (
        jnp.take(mp.reshape(nt, data_tile), ids, axis=0)
        & live_slot[:, None]
    ).reshape(-1)
    hits_sel = tube_select(
        gx, gy, gt, gm, tube_x, tube_y, tube_t, radius_m, half_window_ms,
        data_tile=data_tile,
    )
    out = jnp.zeros((nt, data_tile), bool)
    out = out.at[ids].max(hits_sel.reshape(cap, data_tile))
    return out.reshape(-1)[:n] & mask, overflow


def tube_margins(tube_y, radius_m) -> Tuple[float, float]:
    """Conservative degree margins covering a `radius_m` geodesic reach:
    1 deg latitude >= 110574 m everywhere; longitude degrees shrink by
    cos(lat), evaluated at the highest latitude the corridor can reach."""
    rmax = float(np.max(np.asarray(radius_m)))
    margin_lat = rmax / 110574.0 * 1.01
    lat_max = float(np.max(np.abs(np.asarray(tube_y))))
    # a corridor whose reach includes a pole spans EVERY longitude (a
    # hard 89.5-deg clamp under-margined polar corridors and silently
    # dropped true matches — round-4 review, reproduced at 89.8N)
    pole_dist_m = max(90.0 - lat_max, 0.0) * 110574.0
    if rmax * 1.01 >= pole_dist_m:
        return 360.0, float(margin_lat)
    lat_reach = lat_max + margin_lat  # provably < 90 here
    margin_lon = min(
        360.0,
        rmax / (111320.0 * np.cos(np.radians(lat_reach))) * 1.01,
    )
    return float(margin_lon), float(margin_lat)


def tube_select_pruned(
    x, y, t, mask,
    tube_x, tube_y, tube_t, radius_m, half_window_ms,
    data_tile: int = 8192,
    tile_capacity: "int | None" = None,
) -> Tuple[jax.Array, "int"]:
    """`tube_select` scanning only data tiles whose envelope intersects
    the corridor's per-segment reach (bbox + time window) — the VERDICT
    r3 tile-pruning pass for config 5. Exact for any input order (pruned
    tiles provably cannot match); the win requires store (Z) order where
    tile envelopes are tight.

    Returns (bool [N] hits, capacity_used). tile_capacity=None
    calibrates with one scalar fetch; on overflow the dense kernel runs
    instead and capacity_used = -1 (callers drop their cached value, as
    with knn_sparse_auto)."""
    margin_lon, margin_lat = tube_margins(tube_y, radius_m)
    T = tube_x.shape[0]
    radius_b = jnp.broadcast_to(jnp.asarray(radius_m, jnp.float32), (T,))
    window_b = jnp.broadcast_to(jnp.asarray(half_window_ms, jnp.int64), (T,))
    if tile_capacity is None:
        hits, ov = _tube_pruned_call(
            x, y, t, mask, tube_x, tube_y, tube_t, radius_b, window_b,
            margin_lon, margin_lat, data_tile=data_tile,
            tile_capacity=max(
                64, -(-x.shape[0] // data_tile) // 4
            ),
        )
        if not bool(np.asarray(ov)):
            return hits, max(64, -(-x.shape[0] // data_tile) // 4)
        tile_capacity = -(-x.shape[0] // data_tile)  # all tiles
    hits, ov = _tube_pruned_call(
        x, y, t, mask, tube_x, tube_y, tube_t, radius_b, window_b,
        margin_lon, margin_lat, data_tile=data_tile,
        tile_capacity=tile_capacity,
    )
    if bool(np.asarray(ov)):
        return (
            tube_select(x, y, t, mask, tube_x, tube_y, tube_t,
                        radius_b, window_b, data_tile=data_tile),
            -1,
        )
    return hits, tile_capacity


def tube_select_pruned_sharded(
    mesh: Mesh,
    x, y, t, mask,
    tube_x, tube_y, tube_t, radius_m, half_window_ms,
    data_tile: int = 8192,
    tile_capacity: int = 64,
) -> Tuple[jax.Array, jax.Array]:
    """Tile-pruned tube select with data sharded over the mesh (tube
    replicated, result sharded like the data — pure map, plus one tiny
    all_gather for the overflow flag). Returns (hits sharded [N],
    overflow — True if ANY shard exceeded tile_capacity; callers MUST
    then fall back to tube_select_sharded)."""
    T = tube_x.shape[0]
    margin_lon, margin_lat = tube_margins(np.asarray(tube_y), radius_m)
    radius_b = jnp.broadcast_to(jnp.asarray(radius_m, jnp.float32), (T,))
    window_b = jnp.broadcast_to(jnp.asarray(half_window_ms, jnp.int64), (T,))

    @functools.partial(
        _shard_map,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            P(), P(), P(), P(), P(),
        ),
        out_specs=(P(SHARD_AXIS), P()),
        check_vma=False,  # ov_any is replicated by construction
    )
    def run(x, y, t, m, tx, ty, tt, tr, tw):
        hits, ov = _tube_pruned_call(
            x, y, t, m, tx, ty, tt, tr, tw, margin_lon, margin_lat,
            data_tile=data_tile, tile_capacity=tile_capacity,
        )
        return hits, jnp.any(jax.lax.all_gather(ov, SHARD_AXIS))

    return run(x, y, t, mask, tube_x, tube_y, tube_t, radius_b, window_b)


def tube_select_sharded(
    mesh: Mesh,
    x, y, t, mask,
    tube_x, tube_y, tube_t, radius_m, half_window_ms,
    tube_tile: int = 2048,
):
    """Data sharded over the mesh; the tube (small) is replicated. The result
    mask stays sharded like the data — no collective needed (pure map)."""

    @functools.partial(
        _shard_map,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            P(), P(), P(), P(), P(),
        ),
        out_specs=P(SHARD_AXIS),
    )
    def run(x, y, t, m, tx, ty, tt, tr, tw):
        return tube_select(x, y, t, m, tx, ty, tt, tr, tw, tube_tile=tube_tile)

    return run(
        x, y, t, mask,
        tube_x, tube_y, tube_t,
        jnp.broadcast_to(jnp.asarray(radius_m, jnp.float32), tube_x.shape),
        jnp.broadcast_to(jnp.asarray(half_window_ms, jnp.int64), tube_x.shape),
    )
