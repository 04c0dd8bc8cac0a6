"""k-nearest-neighbor kernels: tiled brute-force haversine + sharded merges.

Parity: geomesa-process KNearestNeighborSearchProcess (knn/) [upstream,
unverified]. The reference's windowed expand-and-requery search exists to
avoid scanning the world from a key-value store; on TPU the economics invert —
a dense tiled all-pairs haversine over the (index-pruned) candidate batch is
exact by construction, so there is no radius iteration and no recall risk.
Recall@k parity is therefore structural: every kernel here is brute-force
over whatever candidates it is given.

Three execution shapes (SURVEY.md §5.7's "ring-topk replaces ring-attention"):

- `knn`          — single device, queries tiled through VMEM via lax.map.
- `knn_sharded`  — data sharded over the mesh axis; per-shard local top-k,
                   then all_gather(k·D candidates) + re-top-k. One collective,
                   exact. The merge is the TPU analog of the reference's
                   client-side fan-in of per-tablet results (C25).
- `knn_ring`     — queries AND data sharded; data shards rotate by ppermute
                   around the ring while each device folds the visiting shard
                   into its running top-k. O(D) steps, constant memory: the
                   long-context/feature-set-scaling shape.

Distances are f32 by default (~meter-scale resolution at Earth radius);
ties at f32 resolution can reorder equidistant neighbors vs an f64 oracle —
recall tests treat within-tolerance distance ties as equivalent.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.lax import pcast as _pcast
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from geomesa_tpu.engine.geodesy import haversine_m
from geomesa_tpu.parallel.mesh import SHARD_AXIS

INF = jnp.float32(jnp.inf)


def _topk_smallest(d: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """top-k smallest along the last axis -> (dists, indices).

    If fewer than k candidates exist (tiny shard, k > rows), the result is
    padded with +inf distances so downstream merges stay shape-stable.
    """
    kk = min(k, d.shape[-1])
    neg, idx = jax.lax.top_k(-d, kk)
    if kk < k:
        pad = [(0, 0)] * (d.ndim - 1) + [(0, k - kk)]
        neg = jnp.pad(neg, pad, constant_values=-jnp.inf)
        idx = jnp.pad(idx, pad)
    return -neg, idx


def _twolevel_smallest(
    d: jax.Array, m: int, block: int = 128
) -> Tuple[jax.Array, jax.Array]:
    """Exact top-m smallest over the last axis via two-level block selection.

    Level 1 takes per-`block` minima and picks the m blocks with the
    smallest minima; level 2 takes the exact top-m over those m·block
    gathered elements. Exactness: if a block holding a true top-m element e
    were NOT picked, then m picked blocks each have a minimum <= e, i.e. m
    elements <= e, so e has rank > m — contradiction. (Ties may swap
    equal-valued candidates, exactly as lax.top_k itself may.)

    Why: lax.top_k over a million-lane axis is a full sort and dominates the
    streamed kNN fold (~4.6x the matmul cost measured on v5e); the block-min
    reduction is a cheap VPU pass over the same data, and the tail top-k
    runs on m·block lanes instead of N.
    """
    n = d.shape[-1]
    nb = n // block
    if nb * block != n or nb < m or n <= 4 * m:
        return _topk_smallest(d, m)
    lead = d.shape[:-1]
    blk = d.reshape(*lead, nb, block)
    bmin = blk.min(axis=-1)
    _, bidx = jax.lax.top_k(-bmin, m)  # [..., m] winning blocks
    g = jnp.take_along_axis(blk, bidx[..., None], axis=-2)
    vals, within = _topk_smallest(g.reshape(*lead, m * block), m)
    blk_of = jnp.take_along_axis(bidx, within // block, axis=-1)
    return vals, blk_of * block + (within % block)


@functools.partial(jax.jit, static_argnames=("k", "query_tile", "data_tile"))
def knn(
    qx: jax.Array,
    qy: jax.Array,
    dx: jax.Array,
    dy: jax.Array,
    mask: jax.Array,
    k: int,
    query_tile: int = 1024,
    data_tile: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Exact kNN: [Q] query points vs [N] masked data points.

    Returns (dists [Q,k] meters, indices [Q,k] into the data arrays).
    Invalid/masked data points get +inf distance (index still in range).

    Both axes are tiled: queries via lax.map, data via a lax.scan that folds
    each [query_tile, data_tile] distance block into a running top-k — peak
    memory is O(query_tile · data_tile), never O(Q · N), so GDELT-scale N
    streams through HBM instead of materializing a multi-GB block. Folding
    per-tile top-ks is exact (the global top-k is a subset of the union of
    tile top-ks — the same argument as the cross-shard merge below).
    """
    q = qx.shape[0]
    n = dx.shape[0]
    if data_tile is None:
        # cap the distance block at ~128M lanes (512MB f32): with two-level
        # selection the fold is bandwidth-bound, and fewer/larger blocks
        # measurably beat smaller ones (v5e sweep: 2^21 lanes/row ~ -20%)
        data_tile = max(k, min(n, (1 << 27) // max(query_tile, 1)))
    pad = (-q) % query_tile
    qxp = jnp.pad(qx, (0, pad))
    qyp = jnp.pad(qy, (0, pad))
    tiles_x = qxp.reshape(-1, query_tile)
    tiles_y = qyp.reshape(-1, query_tile)

    dpad = (-n) % data_tile
    dxp = jnp.pad(dx, (0, dpad)).reshape(-1, data_tile)
    dyp = jnp.pad(dy, (0, dpad)).reshape(-1, data_tile)
    mp = jnp.pad(mask, (0, dpad)).reshape(-1, data_tile)
    n_dtiles = dxp.shape[0]
    dist_dtype = jnp.promote_types(jnp.promote_types(qx.dtype, dx.dtype), jnp.float32)

    def tile(args):
        tx, ty = args

        def fold(carry, xs):
            bd, bi = carry
            dxt, dyt, mt, base = xs
            d = haversine_m(tx[:, None], ty[:, None], dxt[None, :], dyt[None, :])
            d = jnp.where(mt[None, :], d, INF)
            ld, li = _twolevel_smallest(d, k)
            # clamp padded-lane indices into range — their distances are
            # +inf so they never displace real neighbors, but the contract
            # is "index still in range" even for unfilled slots
            gi = jnp.minimum((li + base).astype(jnp.int32), n - 1)
            pool_d = jnp.concatenate([bd, ld], axis=1)
            pool_i = jnp.concatenate([bi, gi], axis=1)
            nd, sel = _topk_smallest(pool_d, k)
            ni = jnp.take_along_axis(pool_i, sel, axis=1)
            return (nd, ni), None

        # derive the init from the inputs so it inherits their varying-
        # mesh-axes tag — a plain constant init breaks lax.scan's carry
        # typing when knn runs inside a shard_map (ring/sharded callers)
        vzero = jnp.sum(dx[:1] * 0).astype(dist_dtype) + jnp.sum(tx[:1] * 0).astype(dist_dtype)
        init = (
            jnp.full((query_tile, k), jnp.inf, dist_dtype) + vzero,
            jnp.zeros((query_tile, k), jnp.int32) + vzero.astype(jnp.int32),
        )
        bases = (jnp.arange(n_dtiles) * data_tile).astype(jnp.int32)
        (bd, bi), _ = jax.lax.scan(fold, init, (dxp, dyp, mp, bases))
        return bd, bi

    dists, idx = jax.lax.map(tile, (tiles_x, tiles_y))
    return (
        dists.reshape(-1, k)[:q],
        idx.reshape(-1, k)[:q],
    )


def _unit3(lon: jax.Array, lat: jax.Array) -> jax.Array:
    """[N] lon/lat degrees -> [N,3] unit vectors on the sphere (f32)."""
    rlon = jnp.radians(lon.astype(jnp.float32))
    rlat = jnp.radians(lat.astype(jnp.float32))
    cl = jnp.cos(rlat)
    return jnp.stack([cl * jnp.cos(rlon), cl * jnp.sin(rlon), jnp.sin(rlat)], -1)


def _morton16(lon: jax.Array, lat: jax.Array) -> jax.Array:
    """Z-order key from 16-bit-quantized lon/lat (device-side, jit-safe)."""
    qx = jnp.clip(((lon + 180.0) / 360.0 * 65535.0), 0, 65535).astype(jnp.uint32)
    qy = jnp.clip(((lat + 90.0) / 180.0 * 65535.0), 0, 65535).astype(jnp.uint32)

    def spread(v):
        v = (v | (v << 8)) & jnp.uint32(0x00FF00FF)
        v = (v | (v << 4)) & jnp.uint32(0x0F0F0F0F)
        v = (v | (v << 2)) & jnp.uint32(0x33333333)
        v = (v | (v << 1)) & jnp.uint32(0x55555555)
        return v

    return spread(qx) | (spread(qy) << 1)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "query_tile", "data_tile", "margin", "with_flags", "presorted"
    ),
)
def knn_mxu(
    qx: jax.Array,
    qy: jax.Array,
    dx: jax.Array,
    dy: jax.Array,
    mask: jax.Array,
    k: int,
    query_tile: int = 64,
    data_tile: Optional[int] = None,
    margin: Optional[int] = None,
    with_flags: bool = False,
    presorted: bool = False,
):
    """kNN via the MXU: centered chord-distance matmul + exact refine.

    Same contract as `knn`. The great-circle distance is monotonic in the
    3D chord distance, so top-k by smallest chord^2 equals top-k by
    smallest haversine. With points as unit vectors, chord^2 = 2 - 2 q.d
    cancels catastrophically in f32 for nearby points (every dot rounds to
    1.0 inside a ~3 km cluster). Instead both sides are translated by the
    query tile's centroid c and

        chord^2 = |q-c|^2 + |d-c|^2 - 2 (q-c).(d-c)

    — translation-invariant and exact in infinite precision, while every
    operand now scales with distance-from-centroid, so f32 resolution is
    relative to the local spread rather than to 1.0. The cross term is a
    [Q,3]x[3,N] matmul on the MXU (~3 MACs/pair at systolic-array rate vs
    ~20 VPU transcendental ops/pair for direct haversine); the norms are
    cheap elementwise VPU work.

    Accuracy model (documented, tested): the f32 rounding noise in chord^2
    is ~6e-8 * r^2 for r = the query TILE's radius in radians. Queries are
    therefore Z-order-sorted internally so each tile of `query_tile`
    (default 64) consecutive queries is as spatially compact as the query
    distribution allows, the candidate pool keeps a top-M margin
    (M = max(4k, 64)) per query, and the final k come from EXACT haversine
    over those M gathered candidates. A true neighbor can only be lost when
    MORE than M-k data points sit inside the noise band around the k-th
    distance — i.e. a meters-dense data cluster queried from a tile whose
    other queries are 100s of km away (the sorted-order tile that straddles
    a cluster boundary). For guaranteed exactness, `with_flags=True` also
    returns a per-query bool that is True whenever the noise bound CANNOT
    prove the result exact: the refined pool's chord^2 span is compared
    against 2B for B = a conservative multiple of eps*r_tile^2. Callers
    (the KNN process does this) re-run flagged queries on the exact
    haversine path — typically none, or only the handful in boundary tiles.

    Small query sets (Q < 128) fall back to the exact haversine path: with
    so few MXU rows the kernel is HBM-bandwidth-bound either way, so the
    matmul buys nothing and tile compactness cannot be established.
    """
    q = qx.shape[0]
    n = dx.shape[0]
    if q < 128:
        fd, fi = knn(qx, qy, dx, dy, mask, k=k,
                     query_tile=min(query_tile, max(q, 1)), data_tile=data_tile)
        return (fd, fi, jnp.zeros(q, bool)) if with_flags else (fd, fi)
    m = margin if margin is not None else max(4 * k, 64)
    m = min(m, n) if n else m
    if data_tile is None:
        data_tile = max(m, min(n, (1 << 27) // max(query_tile, 1)))
    # block-minima layout needs whole 128-lane blocks per data tile
    data_tile = -(-data_tile // 128) * 128

    # compact tiles: process queries in Z-order, un-permute at the end.
    # presorted=True lets loop callers (knn_ring) sort once outside.
    if presorted:
        inv = None
    else:
        order = jnp.argsort(_morton16(qx, qy))
        inv = jnp.argsort(order)
        qx = jnp.take(qx, order)
        qy = jnp.take(qy, order)

    pad = (-q) % query_tile
    # edge-pad so padded lanes don't drag the tile centroid off-cluster
    qxp = jnp.pad(qx, (0, pad), mode="edge") if q else jnp.pad(qx, (0, pad))
    qyp = jnp.pad(qy, (0, pad), mode="edge") if q else jnp.pad(qy, (0, pad))
    qu = _unit3(qxp, qyp)
    tiles_q = qu.reshape(-1, query_tile, 3)

    dpad = (-n) % data_tile
    du = _unit3(jnp.pad(dx, (0, dpad)), jnp.pad(dy, (0, dpad)))
    dut = du.reshape(-1, data_tile, 3)
    mp = jnp.pad(mask, (0, dpad)).reshape(-1, data_tile)
    n_dtiles = dut.shape[0]
    BIG = jnp.float32(8.0)  # > max chord^2 (4.0)

    # deferred block selection: the scan emits only per-128-lane block
    # minima (which XLA fuses into the matmul epilogue — the [Q, N] chord^2
    # matrix never reaches HBM), the m winning blocks per query are picked
    # ONCE over the accumulated minima, and chord^2 is recomputed for just
    # those m·128 lanes. This replaces a per-scan-step top-k + pool merge
    # that cost ~3.5x the fused pass at GDELT scale. Exactness is the
    # two-level argument: if a true top-m element's block were unpicked, m
    # picked blocks each hold an element <= it, so its rank exceeds m.
    BLK = 128
    nb_tile = data_tile // BLK
    du_flat = du  # [n_padded, 3]
    mp_flat = jnp.pad(mask, (0, dpad))

    def tile(tq):
        c = tq.mean(axis=0)
        tqc = tq - c
        nq = jnp.sum(tqc * tqc, axis=-1)  # [query_tile]
        r2_tile = jnp.max(nq)  # squared tile radius, for the noise bound
        # augmented queries [tqc | 1]: one matmul emits the entire per-pair
        # ranking key nd - 2 q.d (chord^2 minus the per-query constant nq,
        # which cannot change ranks within a query row), so the VPU's only
        # [Q, N] work is the block-min compare
        aug_q = jnp.concatenate(
            [tqc, jnp.ones((query_tile, 1), tqc.dtype)], axis=1
        )

        def fold(_, xs):
            dt, mt = xs
            dtc = dt - c
            nd = jnp.sum(dtc * dtc, axis=-1)  # [data_tile]
            # masked rows carry a huge additive term instead of a [Q, N]
            # where(): 1e9 dwarfs any real key (|nd - 2 q.d| <= 12)
            ndm = jnp.where(mt, nd, jnp.float32(1e9))
            aug_d = jnp.concatenate([-2.0 * dtc, ndm[:, None]], axis=1)
            key = jax.lax.dot_general(
                aug_q, aug_d, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
            )  # [query_tile, data_tile] = nd - 2 q.d (+1e9 where masked)
            bmin = key.reshape(query_tile, nb_tile, BLK).min(axis=-1)
            return None, bmin

        _, minima = jax.lax.scan(fold, None, (dut, mp))
        # [n_dtiles, query_tile, nb_tile] -> [query_tile, total_blocks]
        minima = minima.transpose(1, 0, 2).reshape(query_tile, -1)
        mb = min(m, minima.shape[-1])
        _, blk_ids = _twolevel_smallest(minima, mb)  # [query_tile, mb]

        # recompute chord^2 for the winning blocks only (same centered
        # arithmetic, so the noise model and certificate are unchanged)
        lane = (blk_ids[:, :, None] * BLK
                + jnp.arange(BLK, dtype=jnp.int32)).reshape(query_tile, -1)
        gd = jnp.take(du_flat, lane, axis=0)  # [query_tile, mb*BLK, 3]
        gm = jnp.take(mp_flat, lane)
        gdc = gd - c
        nd_g = jnp.sum(gdc * gdc, axis=-1)
        s_g = jnp.einsum("qd,qjd->qj", tqc, gdc,
                         precision=jax.lax.Precision.HIGHEST)
        chord2_g = nq[:, None] + nd_g - 2.0 * s_g
        chord2_g = jnp.where(gm, chord2_g, BIG)
        bs, within = _topk_smallest(chord2_g, m)
        bi = jnp.minimum(
            jnp.take_along_axis(lane, within, axis=1).astype(jnp.int32), n - 1
        )
        return bs, bi, jnp.broadcast_to(r2_tile, (tq.shape[0],))

    chord2, cidx, r2 = jax.lax.map(tile, tiles_q)
    chord2 = chord2.reshape(-1, m)[:q]
    cidx = cidx.reshape(-1, m)[:q]
    r2 = r2.reshape(-1)[:q]

    # exact refine: haversine over the gathered M candidates per query
    cx = jnp.take(dx, cidx)
    cy = jnp.take(dy, cidx)
    dist_dtype = jnp.promote_types(jnp.promote_types(qx.dtype, dx.dtype), jnp.float32)
    d = haversine_m(
        qx[:, None].astype(dist_dtype), qy[:, None].astype(dist_dtype),
        cx.astype(dist_dtype), cy.astype(dist_dtype),
    )
    # masked/unfilled slots carry chord2 == BIG (8.0); legitimate points can
    # reach chord2 == 4.0 exactly at a query's antipode, so the cut must sit
    # strictly between 4+noise and BIG or antipodal neighbors read as masked
    d = jnp.where(chord2 >= 6.0, INF, d)
    fd, sel = _topk_smallest(d, k)
    fi = jnp.take_along_axis(cidx, sel, axis=1)
    fd_out = fd if inv is None else jnp.take(fd, inv, axis=0)
    fi_out = fi if inv is None else jnp.take(fi, inv, axis=0)
    if not with_flags:
        return fd_out, fi_out

    # exactness certificate: an excluded point's true chord^2 exceeds the
    # pool's selection threshold minus the rounding-noise bound B; if the
    # exact k-th..M-th chord^2 span is wider than 2B, no excluded point can
    # beat the k-th neighbor and the result is provably exact.
    from geomesa_tpu.engine.geodesy import EARTH_RADIUS_M

    EPS = jnp.float32(6e-8)  # f32 ulp at ~1 (matmul/norm rounding)
    KAPPA = jnp.float32(8.0)  # roundings of magnitude <= eps * r^2 each
    ETA = jnp.float32(1.3e-7)  # unit-vector f32 quantization (per point)
    finite = jnp.isfinite(d)
    has_unfilled = jnp.any(~finite, axis=1)  # pool held every candidate
    d_M = jnp.max(jnp.where(finite, d, -jnp.inf), axis=1)
    chord_k = 2.0 * jnp.sin(fd[:, -1] / (2.0 * EARTH_RADIUS_M))
    chord_M = 2.0 * jnp.sin(jnp.where(jnp.isfinite(d_M), d_M, 0.0)
                            / (2.0 * EARTH_RADIUS_M))
    B = KAPPA * EPS * r2 + 8.0 * ETA * chord_k
    uncertain = (
        ~has_unfilled
        & (chord_M * chord_M - chord_k * chord_k < 2.0 * B)
    )
    if inv is not None:
        uncertain = jnp.take(uncertain, inv, axis=0)
    return fd_out, fi_out, uncertain


@functools.partial(
    jax.jit, static_argnames=("k", "capacity", "impl", "query_tile")
)
def knn_compact(
    qx: jax.Array,
    qy: jax.Array,
    dx: jax.Array,
    dy: jax.Array,
    mask: jax.Array,
    k: int,
    capacity: int,
    impl: str = "mxu",
    query_tile: int = 64,
) -> Tuple[jax.Array, jax.Array]:
    """kNN over the mask's matches only: device-side candidate compaction.

    At GDELT-scale selectivity (a few % of the scanned batch matches the
    predicate) the dominant cost of `knn`/`knn_mxu` is streaming [Q, N]
    distance blocks through HBM for rows the mask rejects anyway. This
    gathers the matching rows into a dense [capacity] candidate array first
    (one `nonzero` pass — the columnar analog of the reference emitting
    index-scan hits before running KNN on them), then runs the kNN kernel on
    the compacted set: distance traffic drops from O(Q·N) to O(Q·count).

    `capacity` must be a static bound >= the match count (callers bucket it
    to the next power of two to stabilize jit cache keys); validity of each
    compacted slot is derived on device from a sentinel, so no count needs
    to cross from the host. Returned indices refer to the ORIGINAL arrays.

    Returns (dists [Q,k], indices [Q,k], overflow scalar bool): `overflow`
    is True iff the match count exceeded `capacity`, in which case the
    result silently dropped the lowest-index matches — callers MUST check
    it and fall back to the full-scan kernel (the round-1 advisor flagged
    the unchecked contract).
    """
    # top_k-based stream compaction: jnp.nonzero(size=...) lowers ~26x
    # slower on TPU (measured 6.3s vs 0.26s at 67M); top_k over
    # where(mask, iota, -1) yields the matched indices (descending order —
    # irrelevant for kNN) at sort-free selection cost
    n = dx.shape[0]
    if n >= (1 << 31):
        # the int32 index iota below wraps past 2^31 rows; callers shard /
        # tile batches far below this (trace-time check, n is static)
        raise ValueError("knn_compact supports n < 2^31 rows per batch")
    capacity = min(capacity, n)  # lax.top_k requires k <= lane count
    overflow = jnp.sum(mask, dtype=jnp.int32) > capacity
    picked = jax.lax.top_k(
        jnp.where(mask, jnp.arange(n, dtype=jnp.int32), -1), capacity
    )[0]
    idx = jnp.maximum(picked, 0)
    valid = picked >= 0
    cx = jnp.take(dx, idx)
    cy = jnp.take(dy, idx)
    if impl == "mxu":
        fd, fi = knn_mxu(qx, qy, cx, cy, valid, k=k, query_tile=query_tile)
    else:
        fd, fi = knn(qx, qy, cx, cy, valid, k=k)
    return fd, jnp.take(idx, fi), overflow


def knn_sharded(
    mesh: Mesh,
    qx: jax.Array,
    qy: jax.Array,
    dx: jax.Array,
    dy: jax.Array,
    mask: jax.Array,
    k: int,
    query_tile: int = 1024,
    impl: str = "haversine",
    debug_check: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Exact kNN with data sharded over the mesh: local top-k + all_gather
    merge. Returns (dists [Q,k], global indices [Q,k]).

    Exactness: each shard's local top-k is exact over its rows; the true
    global top-k is a subset of the union of per-shard top-ks, so the merged
    re-top-k is exact — the same argument as the reference's per-tablet
    aggregation + client merge, with psum-free O(D·Q·k) gather traffic.

    impl: "haversine" (VPU, bit-exact — the merge argument above holds
    unconditionally) or "mxu" (`knn_mxu` without its exactness certificate:
    the local top-k inherits knn_mxu's f32 noise model, so cluster-boundary
    query tiles can mis-rank meters-scale near-ties; use the KNN process or
    impl="haversine" where guaranteed exactness is required).

    debug_check: the out_specs below declare the post-gather re-top-k
    replicated (check_vma=False silences JAX's varying-mesh-axes check,
    which cannot infer it). With debug_check=True the kernel additionally
    all_gathers the FINAL result and asserts on host that every device
    computed bitwise-identical values — pinning the unchecked invariant
    (round-1 review) at the cost of one extra [D, Q, k] gather.
    """
    if impl == "mxu":
        def local(*a, **kw):
            kw["query_tile"] = min(kw.pop("query_tile", 64), 64)
            return knn_mxu(*a, **kw)
    else:
        local = knn
    d_count = mesh.devices.size
    shard_n = dx.shape[0] // d_count

    @functools.partial(
        _shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=(P(), P(), P()) if debug_check else (P(), P()),
        # post-gather re-top-k computes identical values on every device;
        # JAX's varying-mesh-axes check can't infer that, so assert it
        # (debug_check=True verifies the claim at run time)
        check_vma=False,
    )
    def run(qx, qy, dx, dy, mask):
        dists, idx = local(qx, qy, dx, dy, mask, k=k, query_tile=query_tile)
        shard = jax.lax.axis_index(SHARD_AXIS)
        gidx = idx + shard * shard_n
        # [D, Q, k] candidate pools on every device
        all_d = jax.lax.all_gather(dists, SHARD_AXIS)
        all_i = jax.lax.all_gather(gidx, SHARD_AXIS)
        pool_d = jnp.moveaxis(all_d, 0, 1).reshape(dists.shape[0], -1)
        pool_i = jnp.moveaxis(all_i, 0, 1).reshape(dists.shape[0], -1)
        md, mi = _topk_smallest(pool_d, k)
        gi = jnp.take_along_axis(pool_i, mi, axis=1)
        if debug_check:
            # gather every device's final answer and count positions that
            # differ from device 0's — must be 0 when the replication
            # claim holds. Equality (not subtraction): results are
            # +inf-padded when valid matches < k, and inf - inf = NaN
            # would flag agreement as divergence
            gd = jax.lax.all_gather(md, SHARD_AXIS)
            gg = jax.lax.all_gather(gi, SHARD_AXIS)
            div = jnp.sum((gd != gd[0:1]).astype(jnp.int32)) + jnp.sum(
                (gg != gg[0:1]).astype(jnp.int32)
            )
            return md, gi, div
        return md, gi

    if debug_check:
        md, gi, div = run(qx, qy, dx, dy, mask)
        if float(div) != 0.0:
            raise AssertionError(
                "knn_sharded replication invariant violated: devices "
                f"disagree on the merged top-k (divergence {float(div)})"
            )
        return md, gi
    return run(qx, qy, dx, dy, mask)


def knn_compact_sharded(
    mesh: Mesh,
    qx: jax.Array,
    qy: jax.Array,
    dx: jax.Array,
    dy: jax.Array,
    mask: jax.Array,
    k: int,
    capacity: int,
    query_tile: int = 64,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """knn_compact under the data-sharded merge: each shard compacts its
    own matches (static per-shard `capacity`) and runs the MXU kNN over
    them; the per-shard top-ks merge via all_gather exactly as
    `knn_sharded`. Returns (dists [Q,k], global indices [Q,k],
    overflow bool — True if ANY shard's matches exceeded capacity, in
    which case callers MUST fall back to the full sharded scan)."""
    d_count = mesh.devices.size
    shard_n = dx.shape[0] // d_count

    @functools.partial(
        _shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=(P(), P(), P()),
        check_vma=False,  # post-gather re-top-k replicated (see knn_sharded)
    )
    def run(qx, qy, dx, dy, mask):
        fd, fi, ov = knn_compact(
            qx, qy, dx, dy, mask, k=k, capacity=capacity,
            query_tile=query_tile,
        )
        shard = jax.lax.axis_index(SHARD_AXIS)
        gidx = fi + shard * shard_n
        all_d = jax.lax.all_gather(fd, SHARD_AXIS)
        all_i = jax.lax.all_gather(gidx, SHARD_AXIS)
        pool_d = jnp.moveaxis(all_d, 0, 1).reshape(fd.shape[0], -1)
        pool_i = jnp.moveaxis(all_i, 0, 1).reshape(fd.shape[0], -1)
        md, mi = _topk_smallest(pool_d, k)
        gi = jnp.take_along_axis(pool_i, mi, axis=1)
        ov_any = jnp.any(jax.lax.all_gather(ov, SHARD_AXIS))
        return md, gi, ov_any

    return run(qx, qy, dx, dy, mask)


def knn_ring(
    mesh: Mesh,
    qx: jax.Array,
    qy: jax.Array,
    dx: jax.Array,
    dy: jax.Array,
    mask: jax.Array,
    k: int,
    query_tile: int = 1024,
    impl: str = "haversine",
) -> Tuple[jax.Array, jax.Array]:
    """Exact kNN with BOTH queries and data sharded: ring top-k.

    Each device owns a query shard and a data shard; data shards rotate
    around the ring (ppermute) for D steps while every device folds the
    visiting shard into its running top-k. Communication is the data shard
    itself (the ring-attention access pattern), never the QxN distances.
    Returns (dists, global indices) sharded like the queries.

    impl: "haversine" (bit-exact) or "mxu" (knn_mxu's f32 noise model, no
    certificate — see knn_sharded). For mxu the Z-order query sort is
    hoisted out of the ring loop (queries never change between steps).
    """
    use_mxu = impl == "mxu"
    d_count = mesh.devices.size
    shard_n = dx.shape[0] // d_count

    @functools.partial(
        _shard_map,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS),
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
        ),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        check_vma=False,  # fori_loop carry turns varying after step 1
    )
    def run(qx, qy, dx, dy, mask):
        me = jax.lax.axis_index(SHARD_AXIS)
        perm = [(i, (i + 1) % d_count) for i in range(d_count)]

        if use_mxu:
            order = jnp.argsort(_morton16(qx, qy))
            inv = jnp.argsort(order)
            qx = jnp.take(qx, order)
            qy = jnp.take(qy, order)

            def local(qx, qy, dx, dy, mask, k, query_tile):
                return knn_mxu(qx, qy, dx, dy, mask, k=k,
                               query_tile=min(query_tile, 64), presorted=True)
        else:
            local = knn

        def step(i, carry):
            best_d, best_i, dx, dy, mask = carry
            owner = (me - i) % d_count  # whose shard is visiting
            ld, li = local(qx, qy, dx, dy, mask, k=k, query_tile=query_tile)
            gi = (li + owner * shard_n).astype(jnp.int32)
            pool_d = jnp.concatenate([best_d, ld], axis=1)
            pool_i = jnp.concatenate([best_i, gi], axis=1)
            nd, sel = _topk_smallest(pool_d, k)
            ni = jnp.take_along_axis(pool_i, sel, axis=1)
            dx, dy, mask = (
                jax.lax.ppermute(a, SHARD_AXIS, perm) for a in (dx, dy, mask)
            )
            return nd, ni, dx, dy, mask

        q = qx.shape[0]
        dist_dtype = jnp.promote_types(jnp.promote_types(qx.dtype, dx.dtype), jnp.float32)
        # mark the init carry as device-varying (it becomes so after step 1)
        best_d = _pcast(
            jnp.full((q, k), jnp.inf, dist_dtype), SHARD_AXIS, to="varying"
        )
        best_i = _pcast(jnp.zeros((q, k), jnp.int32), SHARD_AXIS, to="varying")
        best_d, best_i, *_ = jax.lax.fori_loop(
            0, d_count, step, (best_d, best_i, dx, dy, mask)
        )
        if use_mxu:
            best_d = jnp.take(best_d, inv, axis=0)
            best_i = jnp.take(best_i, inv, axis=0)
        return best_d, best_i

    return run(qx, qy, dx, dy, mask)
