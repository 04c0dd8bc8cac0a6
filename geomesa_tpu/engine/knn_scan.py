"""Fused scan kNN: Pallas chord-key block-minima + deferred block refine.

Parity role: the server-side scan half of KNearestNeighborSearchProcess
(geomesa-process knn/) — the reference streams index-scan hits through a
per-tablet iterator and merges client-side; here ONE fused device pass
scans the whole candidate batch (SURVEY.md §5.7 feature-set scaling).

Why these kernels exist (measured on v5e, 67M points, 256 queries):
the XLA path (`knn_compact`) pays three separate HBM regimes —
  1. flat `lax.top_k` stream compaction over 67M lanes   ~180 ms
  2. element gather of 4.2M matched rows                  ~90 ms
  3. `knn_mxu`'s scan, whose [Q, data_tile] ranking-key
     matmul output round-trips HBM every fold step       ~20 ms/4.2M
                                                         (~320 ms at 67M)
The dense kernel (`knn_fullscan`) replaces all three with the
flash-attention access pattern: stream coordinate tiles through VMEM,
compute the centered chord ranking key (MXU matmul, K=4) IN VMEM, reduce
each BLK-lane block to its minimum, and emit only the [Q, N/BLK] minima:

  minima = pallas_scan(x, y, maskf)             # one HBM pass, fused
  blocks = two-level top-m over minima          # m winning blocks/query
  refine = exact haversine over m*BLK gathered  # block-granular gather —
           lanes -> top-k                       # measured as fast as a
                                                # contiguous copy

Its wall is the MXU OUTPUT RATE, not HBM: [Q=256] x [N=67M] keys at ~128
results/cycle is ~134 M cycles (~140 ms @ 0.94 GHz) no matter how the
reduction is tuned (measured 122 ms with the VPU reduction overlapped).
Brute force is therefore Q-bound, which is what the SPARSE kernel
(`knn_sparse_scan`) attacks: a scalar-prefetched list of match-bearing
data tiles drives the BlockSpec index maps, so unselected tiles never
leave HBM and the MXU bound scales with sum(selected tiles) instead of N.
On store-ordered (Z-sorted) batches a bbox predicate touches ~selectivity
fraction of tiles; on randomly-ordered batches it degrades to the dense
cost plus one cheap pass (every tile holds a match).

Exactness (both kernels): identical argument to knn_mxu's deferred block
selection — if a true top-m element's block were unpicked, the m picked
blocks each hold an element with key <= it, so its rank exceeds m >= k
(m_blocks >= k is REQUIRED and checked at trace time). The final k always
comes from exact haversine over the gathered candidates, and the
guarantee is noise-independent: it needs only a per-row-monotonic ranking
key, which any f32 rounding of chord^2 still is within each block's min.

The ranking key is the centered augmented form (knn_mxu's derivation):
  key(q, d) = |d-c|^2 - 2 (q-c).(d-c) + (1-mask) * 1e9
monotonic in chord^2 within a query row; c = the query set's mean unit
vector, so f32 resolution scales with distance-from-centroid.

Mosaic constraints that shaped the code (each cost a compile attempt):
64-bit anything is rejected -> trace under jax.enable_x64(False); output
block lane dims must be >=128 or the full array -> DATA_TILE/BLK = 128;
dynamic (fori_loop-indexed) sub-128-lane vector stores don't legalize ->
the chunk sweep is a PYTHON loop (static store offsets), and >8 unrolled
bodies send Mosaic compile time past 10 minutes -> DATA_TILE/CHUNK = 4.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from jax import enable_x64 as _enable_x64
from jax import shard_map as _shard_map
import numpy as np

from geomesa_tpu.engine.geodesy import haversine_m
from geomesa_tpu.engine.knn import _topk_smallest, _twolevel_smallest, _unit3

BLK = 128  # minima granularity: one minimum per BLK data lanes
DATA_TILE = 16384  # lanes per pallas program (output block [Q, 128])
CHUNK = 4096  # key-matrix chunk inside the kernel ([Q, CHUNK] in VMEM)
PENALTY = 1e9  # additive key for masked rows (|key| <= 12 for real rows)


def _chunk_body(aug_q, cx, cy, cz, x_ref, y_ref, m_ref, out_ref, s: int,
                chunk: int, blk: int, extra: float = 0.0):
    """One static chunk: unit vectors + MXU key + blk-lane minima."""
    q = aug_q.shape[0]
    sl = slice(s * chunk, (s + 1) * chunk)
    rlon = jnp.radians(x_ref[0, sl])  # [chunk]
    rlat = jnp.radians(y_ref[0, sl])
    cl = jnp.cos(rlat)
    dx = cl * jnp.cos(rlon) - cx
    dy = cl * jnp.sin(rlon) - cy
    dz = jnp.sin(rlat) - cz
    nd = dx * dx + dy * dy + dz * dz
    ndm = nd + (1.0 - m_ref[0, sl]) * PENALTY + extra  # [chunk]

    # [Q, 4] x [4, chunk] on the MXU: key = ndm - 2 (q-c).(d-c)
    aug_d = jnp.stack([dx, dy, dz, ndm])  # [4, chunk]
    key = jnp.dot(
        aug_q, aug_d,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # [Q, chunk]
    nb = chunk // blk
    out_ref[:, s * nb: (s + 1) * nb] = key.reshape(q, nb, blk).min(axis=-1)


def _make_kernel(data_tile: int, chunk: int, blk: int):
    def _scan_kernel(aug_q_ref, c_ref, x_ref, y_ref, m_ref, out_ref):
        aug_q = aug_q_ref[...]
        cx = c_ref[0, 0]
        cy = c_ref[0, 1]
        cz = c_ref[0, 2]
        # the [Q, data_tile] key matrix would blow VMEM, so the tile is
        # swept in chunk-lane slices (static Python loop — see module
        # docstring for why not fori_loop)
        for s in range(data_tile // chunk):
            _chunk_body(aug_q, cx, cy, cz, x_ref, y_ref, m_ref, out_ref,
                        s, chunk, blk)

    return _scan_kernel


def chord_blockmin(
    qx: jax.Array,
    qy: jax.Array,
    x: jax.Array,
    y: jax.Array,
    maskf: jax.Array,
    blk: int = BLK,
    data_tile: int = DATA_TILE,
    chunk: int = CHUNK,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """One fused pass: [Q] queries x [N] points -> ([Q, N/blk] block
    minima of the centered chord ranking key, [3] centroid). N must be a
    multiple of data_tile; maskf is the predicate mask as f32 0/1."""
    from jax.experimental import pallas as pl

    n = x.shape[0]
    q = qx.shape[0]
    assert n % data_tile == 0, (n, data_tile)
    chunk = min(chunk, data_tile)
    assert data_tile % chunk == 0 and chunk % blk == 0, (
        data_tile, chunk, blk)
    qu = _unit3(qx, qy)  # [Q, 3]
    c = qu.mean(axis=0)  # [3]
    qc = qu - c
    aug_q = jnp.concatenate([-2.0 * qc, jnp.ones((q, 1), jnp.float32)], 1)
    carr = jnp.zeros((1, 128), jnp.float32).at[0, :3].set(c)

    grid = (n // data_tile,)
    out_lanes = data_tile // blk
    # Mosaic rejects 64-bit types; trace with x64 off so index-map and
    # in-kernel literals stay i32/f32 under the repo's global x64 mode
    with _enable_x64(False):
        minima = pl.pallas_call(
            _make_kernel(data_tile, chunk, blk),
            grid=grid,
            in_specs=[
                pl.BlockSpec((q, 4), lambda j: (0, 0)),
                pl.BlockSpec((1, 128), lambda j: (0, 0)),
                pl.BlockSpec((1, data_tile), lambda j: (0, j)),
                pl.BlockSpec((1, data_tile), lambda j: (0, j)),
                pl.BlockSpec((1, data_tile), lambda j: (0, j)),
            ],
            out_specs=pl.BlockSpec((q, out_lanes), lambda j: (0, j)),
            out_shape=jax.ShapeDtypeStruct((q, n // blk), jnp.float32),
            interpret=interpret,
        )(aug_q, carr, x.reshape(1, n), y.reshape(1, n), maskf.reshape(1, n))
    return minima, c


def _make_sparse_kernel(data_tile: int, chunk: int, blk: int):
    """Program p processes the data tile named by the scalar-prefetched
    `ids` array; programs past `nsel` (capacity padding) emit PENALTY
    without touching the MXU."""

    def _kernel(ids_ref, nsel_ref, aug_q_ref, c_ref, x_ref, y_ref, m_ref,
                out_ref):
        from jax.experimental import pallas as pl

        p = pl.program_id(0)

        @pl.when(p < nsel_ref[0])
        def _live():
            aug_q = aug_q_ref[...]
            cx = c_ref[0, 0]
            cy = c_ref[0, 1]
            cz = c_ref[0, 2]
            for s in range(data_tile // chunk):
                _chunk_body(aug_q, cx, cy, cz, x_ref, y_ref, m_ref,
                            out_ref, s, chunk, blk)

        @pl.when(p >= nsel_ref[0])
        def _dead():
            out_ref[...] = jnp.full_like(out_ref, PENALTY)

    return _kernel


def chord_blockmin_sparse(
    qx: jax.Array,
    qy: jax.Array,
    x: jax.Array,
    y: jax.Array,
    maskf: jax.Array,
    tile_ids: jax.Array,
    n_sel: jax.Array,
    blk: int = BLK,
    data_tile: int = DATA_TILE,
    chunk: int = CHUNK,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Sparse block-minima: only the data tiles named by `tile_ids` are
    scanned. tile_ids is a static-capacity [C] int32 array (entries past
    `n_sel` are ignored — their minima come out as +PENALTY). Returns
    ([Q, C * data_tile/blk] minima over the SELECTED tiles in tile_ids
    order, [3] centroid)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = x.shape[0]
    q = qx.shape[0]
    assert n % data_tile == 0, (n, data_tile)
    chunk = min(chunk, data_tile)
    cap = tile_ids.shape[0]
    qu = _unit3(qx, qy)
    c = qu.mean(axis=0)
    qc = qu - c
    aug_q = jnp.concatenate([-2.0 * qc, jnp.ones((q, 1), jnp.float32)], 1)
    carr = jnp.zeros((1, 128), jnp.float32).at[0, :3].set(c)
    out_lanes = data_tile // blk

    with _enable_x64(False):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # tile_ids, n_sel
            grid=(cap,),
            in_specs=[
                pl.BlockSpec((q, 4), lambda p, ids, ns: (0, 0)),
                pl.BlockSpec((1, 128), lambda p, ids, ns: (0, 0)),
                pl.BlockSpec((1, data_tile), lambda p, ids, ns: (0, ids[p])),
                pl.BlockSpec((1, data_tile), lambda p, ids, ns: (0, ids[p])),
                pl.BlockSpec((1, data_tile), lambda p, ids, ns: (0, ids[p])),
            ],
            out_specs=pl.BlockSpec(
                (q, out_lanes), lambda p, ids, ns: (0, p)
            ),
        )
        minima = pl.pallas_call(
            _make_sparse_kernel(data_tile, chunk, blk),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((q, cap * out_lanes), jnp.float32),
            interpret=interpret,
        )(
            tile_ids.astype(jnp.int32),
            jnp.asarray(n_sel, jnp.int32).reshape(1),
            aug_q, carr,
            x.reshape(1, n), y.reshape(1, n), maskf.reshape(1, n),
        )
    return minima, c


def _refine(qx, qy, xf, yf, maskf, orig_blk, n, k, blk, blk_ok=None):
    """Exact haversine over the selected blocks' lanes -> top-k.
    Block-granular gather: rows of blk contiguous lanes (measured as fast
    as a contiguous copy; element gathers are ~50x slower). `blk_ok`
    [Q, mb] masks out selected blocks that are capacity-padding artifacts
    (sparse scan: dead slots alias data tile 0 and would otherwise
    DUPLICATE tile-0 lanes in the pool)."""
    q = qx.shape[0]
    mb = orig_blk.shape[1]
    nb = xf.shape[0] // blk
    xb = xf.reshape(nb, blk)
    yb = yf.reshape(nb, blk)
    vb = maskf.reshape(nb, blk) > 0.5
    gx = jnp.take(xb, orig_blk, axis=0).reshape(q, mb * blk)
    gy = jnp.take(yb, orig_blk, axis=0).reshape(q, mb * blk)
    gv = jnp.take(vb, orig_blk, axis=0).reshape(q, mb * blk)
    if blk_ok is not None:
        gv = gv & jnp.repeat(blk_ok, blk, axis=1)
    lane = (orig_blk[:, :, None] * blk + jnp.arange(blk, dtype=jnp.int32)
            ).reshape(q, mb * blk)

    d = haversine_m(
        qx[:, None].astype(jnp.float32), qy[:, None].astype(jnp.float32),
        gx, gy,
    )
    d = jnp.where(gv & (lane < n), d, jnp.float32(jnp.inf))
    fd, sel = _topk_smallest(d, k)
    fi = jnp.minimum(jnp.take_along_axis(lane, sel, axis=1), n - 1)
    return fd, fi


@functools.partial(
    jax.jit,
    static_argnames=("k", "m_blocks", "blk", "data_tile", "interpret"),
)
def knn_fullscan(
    qx: jax.Array,
    qy: jax.Array,
    x: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    k: int,
    m_blocks: int = 64,
    blk: int = BLK,
    data_tile: int = DATA_TILE,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Exact kNN over the masked batch in one fused dense scan (no
    compaction, no capacity, no host round trip). Same contract as `knn`:
    returns (dists [Q, k] meters, indices [Q, k] into the original
    arrays). m_blocks >= k required (see module docstring); N is padded
    to data_tile internally (padded lanes masked out)."""
    n = x.shape[0]
    q = qx.shape[0]
    if k > m_blocks:  # trace-time contract: exactness needs m >= k
        raise ValueError(
            f"k={k} exceeds m_blocks={m_blocks}: the deferred block "
            "selection only guarantees the top-m_blocks elements"
        )
    pad = (-n) % data_tile
    xf = jnp.pad(x.astype(jnp.float32), (0, pad))
    yf = jnp.pad(y.astype(jnp.float32), (0, pad))
    maskf = jnp.pad(mask.astype(jnp.float32), (0, pad))
    npad = n + pad

    minima, _ = chord_blockmin(
        qx, qy, xf, yf, maskf,
        blk=blk, data_tile=data_tile, interpret=interpret,
    )
    mb = min(m_blocks, npad // blk)
    _, blkid = _twolevel_smallest(minima, mb)  # [Q, mb]
    return _refine(qx, qy, xf, yf, maskf, blkid, n, k, blk)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "m_blocks", "blk", "data_tile", "tile_capacity", "interpret"
    ),
)
def knn_sparse_scan(
    qx: jax.Array,
    qy: jax.Array,
    x: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    k: int,
    tile_capacity: int,
    m_blocks: int = 64,
    blk: int = BLK,
    data_tile: int = DATA_TILE,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Exact kNN over the masked batch scanning ONLY data tiles that hold
    at least one match. Same contract as `knn` plus an overflow flag:
    (dists [Q, k], indices [Q, k], overflow bool scalar).

    The win is proportional to match clustering: on store-ordered
    (Z-sorted) batches a bbox predicate selects a contiguous ~selectivity
    fraction of tiles; on randomly-ordered batches nearly every tile has
    a match and this degrades to the dense kernel plus one cheap pass.
    `tile_capacity` is the static bound on selected tiles (callers bucket
    it pow2 from the planner's selectivity estimate — overshoot is cheap,
    dead programs skip the MXU); if more tiles match, `overflow` is True,
    the top-k silently ignored the highest-id matching tiles, and the
    caller MUST fall back (knn_fullscan). m_blocks >= k required."""
    n = x.shape[0]
    if k > m_blocks:
        raise ValueError(
            f"k={k} exceeds m_blocks={m_blocks}: the deferred block "
            "selection only guarantees the top-m_blocks elements"
        )
    pad = (-n) % data_tile
    xf = jnp.pad(x.astype(jnp.float32), (0, pad))
    yf = jnp.pad(y.astype(jnp.float32), (0, pad))
    maskf = jnp.pad(mask.astype(jnp.float32), (0, pad))
    npad = n + pad
    ntiles = npad // data_tile
    tile_capacity = min(tile_capacity, ntiles)

    # matching tiles (ascending ids), static capacity
    tmatch = maskf.reshape(ntiles, data_tile).max(axis=1) > 0.0
    n_sel = jnp.sum(tmatch.astype(jnp.int32))
    overflow = n_sel > tile_capacity
    picked = jax.lax.top_k(
        jnp.where(tmatch, -jnp.arange(ntiles, dtype=jnp.int32),
                  -(1 << 30)),
        tile_capacity,
    )[0]
    tile_ids = jnp.where(picked > -(1 << 30), -picked, 0)

    minima, _ = chord_blockmin_sparse(
        qx, qy, xf, yf, maskf, tile_ids, n_sel,
        blk=blk, data_tile=data_tile, interpret=interpret,
    )
    bpt = data_tile // blk  # blocks per tile
    mb = min(m_blocks, minima.shape[1])
    vals, selblk = _twolevel_smallest(minima, mb)  # [Q, mb] minima space
    # minima-space block -> original block id. Dead capacity-padding
    # programs emit exactly PENALTY and alias data tile 0 — a selected
    # block is real only if its minimum is below the mask penalty (real
    # matched blocks carry keys <= 12; all-masked and dead blocks >= 1e9)
    blk_ok = vals < jnp.float32(PENALTY / 2)
    orig_blk = jnp.take(tile_ids, selblk // bpt) * bpt + selblk % bpt
    fd, fi = _refine(qx, qy, xf, yf, maskf, orig_blk, n, k, blk,
                     blk_ok=blk_ok)
    return fd, fi, overflow


# f32 scan-ranking error budget (round 5, VERDICT r4 task 10): the fused
# scan ranks by f32 haversine (d = 2R asin(sqrt(a))) over f32-rounded
# coordinates. |d_f32 - d_f64(original coords)| at true distance d:
#   - coordinate rounding: one lat/lon ulp at |coord|<=360 is 2^-24*256 ~
#     1.5e-5 deg ~ 1.7 m of ground shift per endpoint -> ~4 m absolute;
#   - f32 arithmetic in `a`: ~relative error REL_A in a, AMPLIFIED by
#     dd/da = 2R/sin(d/R) — near the antipode sin(d/R) -> 0 and the
#     error reaches km scale (review finding: empirically ~3.9 km at
#     100 km short of the antipode; a flat 4 m + 1e-5*d model falsely
#     certified there). err_m(d) models exactly that amplification:
#     2R*REL_A*sin^2(d/2R)/sin(d/R), which reduces to (REL_A/2)*d for
#     small d and covers the measured antipodal blowup with ~4x margin.
KNN_F32_ABS_M = 4.0
KNN_F32_REL_A = 1e-5  # ~160 ulps of `a` — deliberately loose
_R_EARTH_M = 6_371_000.0


def knn_f32_err_m(d):
    """Upper bound on |f32 scan distance - f64 true distance| at true
    distance d meters (see the model above). Monotone increasing on
    [0, pi*R), which the certificate in knn_exact_refine relies on."""
    d = np.asarray(d, np.float64)
    half = d / (2.0 * _R_EARTH_M)
    s = np.sin(np.clip(2.0 * half, 0.0, np.pi))
    amp = np.where(
        s > 1e-9,
        2.0 * _R_EARTH_M * KNN_F32_REL_A * np.sin(half) ** 2 / s,
        np.inf,  # at/after the antipode nothing is certifiable
    )
    return KNN_F32_ABS_M + amp


def knn_exact_refine(qx_np, qy_np, x_np, y_np, fd, fi, k):
    """Band-refine at the k-th boundary: f64 re-ranking of the k' > k
    candidates a kernel returned, with a certificate that the TRUE top-k
    (by f64 haversine over the ORIGINAL f64 coordinates) lies inside the
    candidate set.

    Args: query/data coords as f64 numpy; fd/fi [Q, k'] f32 distances +
    indices from any scan kernel run with k' = k + pad. Returns
    (d64 [Q, k] sorted, idx [Q, k], certified [Q] bool).

    Certificate: a row NOT returned has f32 distance >= L := the largest
    returned f32 distance. A missed row with true distance D <= B (the
    refined k-th distance, exact f64) would need its f32 distance pushed
    from <= B + err(B) up to >= L (err monotone increasing), so
    L > B + err_m(B) proves no true top-k member was missed. The bound
    decertifies antipodal boundaries by construction — err_m blows up
    exactly where f32 haversine does. Uncertified rows need a caller
    fallback (wider pad or full rescan)."""
    from geomesa_tpu.engine.geodesy import haversine_m_np

    fd = np.asarray(fd)
    fi = np.asarray(fi)
    Q, kp = fd.shape
    assert kp >= k
    d64 = np.empty((Q, kp))
    for i in range(Q):
        d64[i] = np.where(
            np.isfinite(fd[i]),
            haversine_m_np(qx_np[i], qy_np[i], x_np[fi[i]], y_np[fi[i]]),
            np.inf,
        )
    order = np.argsort(d64, axis=1, kind="stable")[:, :k]
    dists = np.take_along_axis(d64, order, axis=1)
    idx = np.take_along_axis(fi, order, axis=1)
    # an inf anywhere in fd means fewer than k' matches exist, so nothing
    # was cut off: L=inf certifies those rows through the same comparison
    L = np.where(np.isfinite(fd).all(1), fd.max(1), np.inf)
    B = dists[:, -1]
    with np.errstate(invalid="ignore"):
        certified = (L > B + knn_f32_err_m(B)) | ~np.isfinite(B)
    return dists, idx, certified


# -- ring-loop kernel variants (docs/SERVING.md "Persistent serve loop") ----
# The persistent serve loop dispatches ONE long-lived executable per
# (kernel, bucket, dtype, mesh_shape) and feeds it query slots from a
# fixed ring of staging buffers. These raw (un-jitted) callables are the
# forms the ExecutableRegistry's ring tier compiles for it: argnums 0/1
# (the slot's qx/qy) are the ONLY per-window inputs — the feature-set
# arguments (x, y, mask) are pre-bound device references the ring
# program re-passes unchanged every window, so XLA sees a stable
# parameter layout and (with donation, non-CPU) reuses the slot HBM
# across windows. The math is knn_sparse_scan / knn_fullscan_tiled
# exactly — a distinct callable only so the ring registration can carry
# its own donation contract without re-keying the base kernels.


def knn_ring_scan(qx, qy, x, y, mask, k, tile_capacity, m_blocks,
                  interpret):
    """Slot-parameterized sparse scan for the ring tier (see above).
    Same contract as `knn_sparse_scan`: (dists, idx, overflow)."""
    return knn_sparse_scan(
        qx, qy, x, y, mask, k=k, tile_capacity=tile_capacity,
        m_blocks=m_blocks, interpret=interpret)


def knn_ring_fullscan(qx, qy, x, y, mask, k, m_blocks, interpret):
    """Slot-parameterized dense scan for the ring tier (see above).
    Same contract as `knn_fullscan_tiled`: (dists, idx)."""
    return knn_fullscan_tiled(
        qx, qy, x, y, mask, k=k, m_blocks=m_blocks, interpret=interpret)


def default_interpret() -> bool:
    """Pallas interpret mode when the default device is CPU (Mosaic
    kernels lower only on TPU) — used by product paths that run the same
    code in CI (virtual CPU devices) and on hardware."""
    return jax.devices()[0].platform == "cpu"


@functools.partial(jax.jit, static_argnames=("data_tile",))
def count_match_tiles(mask: jax.Array, data_tile: int = DATA_TILE):
    """Device count of match-bearing data tiles (the planner's capacity
    calibration input — one i32 scalar reaches the host, not the mask)."""
    n = mask.shape[0]
    pad = (-n) % data_tile
    mf = jnp.pad(mask.astype(jnp.int32), (0, pad))
    return jnp.sum(
        (mf.reshape(-1, data_tile).max(axis=1) > 0).astype(jnp.int32)
    )


def capacity_bucket(tiles_hit: int, slack: float = 1.25,
                    floor: int = 64) -> int:
    """pow2 capacity bucket from a tiles-hit measurement/estimate: slack
    absorbs drift between calibration and the live query (overshoot is
    cheap — dead capacity programs skip the MXU), pow2 keeps the pallas
    jit cache stable across queries."""
    need = max(int(tiles_hit * slack), 1)
    return max(floor, 1 << int(np.ceil(np.log2(need))))


def knn_sparse_launch(
    qx: jax.Array,
    qy: jax.Array,
    x: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    k: int,
    tile_capacity: "int | None" = None,
    m_blocks: int = 64,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, int]:
    """Async half of `knn_sparse_auto`: calibrate capacity if the caller
    has no estimate (one device scalar fetch — the only sync here), then
    DISPATCH the sparse scan and return device-resident
    (dists, idx, overflow, tile_capacity) without reading anything back.
    JAX's async dispatch means the kernel executes while the caller's
    host thread moves on — the serve pipeline launches window N+1's
    transfer behind this. `knn_sparse_finish` completes the contract."""
    if tile_capacity is None:
        tile_capacity = capacity_bucket(int(np.asarray(
            count_match_tiles(mask))))
    fd, fi, ov = knn_sparse_scan(
        qx, qy, x, y, mask, k=k, tile_capacity=tile_capacity,
        m_blocks=m_blocks, interpret=interpret,
    )
    return fd, fi, ov, tile_capacity


def knn_sparse_finish(
    fd, fi, ov,
    qx: jax.Array,
    qy: jax.Array,
    x: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    k: int,
    tile_capacity: int,
    m_blocks: int = 64,
    interpret: bool = False,
    extra=(),
) -> tuple:
    """Sync half: ONE transfer for results + overflow flag (+ any
    `extra` device values riding the same fetch — the serve path's fused
    count scalar), falling back to the dense fullscan on overflow
    exactly like `knn_sparse_auto`. Returns
    (dists np, idx np, capacity_used, extra_host tuple)."""
    # ONE transfer: fetching ov alone first would serialize a second
    # device round trip before the caller's own result fetch
    fd, fi, ov, *extra_host = jax.device_get((fd, fi, ov) + tuple(extra))
    if bool(ov):
        fd, fi = jax.device_get(knn_fullscan(
            qx, qy, x, y, mask, k=k, m_blocks=m_blocks,
            interpret=interpret))
        return fd, fi, -1, tuple(extra_host)
    return fd, fi, tile_capacity, tuple(extra_host)


def knn_sparse_auto(
    qx: jax.Array,
    qy: jax.Array,
    x: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    k: int,
    tile_capacity: "int | None" = None,
    m_blocks: int = 64,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, int]:
    """The framework-facing sparse kNN: calibrate capacity if the caller
    has no estimate (one device scalar fetch), run the sparse scan, and
    on overflow fall back to the dense fullscan (documented contract of
    `knn_sparse_scan`). Returns (dists, idx, capacity_used) with dists/
    idx as HOST numpy arrays (results and the overflow flag come back in
    one transfer). Callers cache capacity_used across queries and only
    pay calibration again after an overflow (capacity_used == -1 signals
    the fallback ran, so the next query recalibrates). Composed from the
    launch/finish halves so the serial path and the serve pipeline run
    byte-identical kernel sequences."""
    fd, fi, ov, tile_capacity = knn_sparse_launch(
        qx, qy, x, y, mask, k=k, tile_capacity=tile_capacity,
        m_blocks=m_blocks, interpret=interpret,
    )
    fd, fi, cap, _ = knn_sparse_finish(
        fd, fi, ov, qx, qy, x, y, mask, k=k, tile_capacity=tile_capacity,
        m_blocks=m_blocks, interpret=interpret,
    )
    return fd, fi, cap


def knn_sparse_sharded(
    mesh,
    qx: jax.Array,
    qy: jax.Array,
    dx: jax.Array,
    dy: jax.Array,
    mask: jax.Array,
    k: int,
    tile_capacity: int,
    m_blocks: int = 64,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """`knn_sparse_scan` under the data-sharded all_gather merge (same
    shape as `knn.knn_compact_sharded`): each shard scans only its own
    match-bearing tiles (static per-shard `tile_capacity`), per-shard
    top-ks merge exactly. Returns (dists [Q,k], global indices [Q,k],
    overflow — True if ANY shard overflowed its tile capacity, in which
    case the caller MUST fall back to a dense sharded scan)."""
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
        check_vma=False,  # post-gather re-top-k replicated (see knn_sharded)
    )
    def run(qx, qy, dx, dy, mask):
        fd, fi, ov = knn_sparse_scan(
            qx, qy, dx, dy, mask, k=k, tile_capacity=tile_capacity,
            m_blocks=m_blocks, interpret=interpret,
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


def shard_match_tiles(mask: jax.Array, n_shards: int,
                      data_tile: int = DATA_TILE) -> jax.Array:
    """MAX over shards of the per-shard match-bearing tile count — the
    serve mesh path's capacity calibration input (one i32 scalar reaches
    the host, exactly like `count_match_tiles` on the serial path).
    Each shard pads its rows to `data_tile` independently inside
    `knn_sparse_scan`, so the per-shard tiling here mirrors that."""
    n = mask.shape[0]
    s = n // n_shards
    pad = (-s) % data_tile
    m = mask.astype(jnp.int32).reshape(n_shards, s)
    if pad:
        m = jnp.pad(m, ((0, 0), (0, pad)))
    per_shard = jnp.sum(
        (m.reshape(n_shards, -1, data_tile).max(axis=2) > 0)
        .astype(jnp.int32), axis=1)
    return jnp.max(per_shard)


def _shard_merge_topk(fd, fi, shard_n: int, k: int):
    """The mesh-serving merge epilogue, shared by the sparse program
    and its fullscan overflow fallback (a divergence here would break
    the bit-identity contract exactly on the rarely-taken overflow
    path): local indices lift to global (`local + shard * shard_n` —
    the mesh superbatch keeps the serial layout, so the global index
    IS the serial index), every shard's top-k pools via all_gather,
    and one re-top-k picks the global k-smallest."""
    import jax

    from geomesa_tpu.engine.knn import _topk_smallest
    from geomesa_tpu.parallel.mesh import SHARD_AXIS

    shard = jax.lax.axis_index(SHARD_AXIS)
    gidx = fi + shard * shard_n
    all_d = jax.lax.all_gather(fd, SHARD_AXIS)
    all_i = jax.lax.all_gather(gidx, SHARD_AXIS)
    pool_d = jnp.moveaxis(all_d, 0, 1).reshape(fd.shape[0], -1)
    pool_i = jnp.moveaxis(all_i, 0, 1).reshape(fd.shape[0], -1)
    md, mi = _topk_smallest(pool_d, k)
    gi = jnp.take_along_axis(pool_i, mi, axis=1)
    return md, gi


def make_knn_serve_sharded(mesh):
    """Build the mesh-serving kNN program for `mesh` (docs/SERVING.md
    "Sharded serving"): ONE shard_map program in which every chip runs
    `knn_sparse_scan` over its own resident rows, per-shard top-ks merge
    via all_gather + re-top-k, the overflow flags OR-reduce, and (when
    `want_count` is set) the cross-kind fused COUNT psum-reduces over
    ICI — the paper's "batched JAX reductions with psum over ICI"
    shape. Global indices are `local + shard * shard_rows`, which under
    the mesh superbatch's serial-layout contract makes results
    bit-identical to the single-chip kernel (tests/test_mesh_serve.py).

    Returns a plain callable suitable for ExecutableRegistry
    registration (`registry.mesh_variant`); statics are keyword-only so
    the AOT key covers (bucket, dtype, k, capacity, mesh shape)."""
    from jax.sharding import PartitionSpec as P

    from geomesa_tpu.parallel.mesh import SHARD_AXIS

    d_count = int(mesh.devices.size)

    def run(qx, qy, x, y, mask, k, tile_capacity, m_blocks,
            want_count, interpret):
        shard_n = x.shape[0] // d_count

        @functools.partial(
            _shard_map,
            mesh=mesh,
            in_specs=(P(), P(), P(SHARD_AXIS), P(SHARD_AXIS),
                      P(SHARD_AXIS)),
            out_specs=((P(), P(), P(), P()) if want_count
                       else (P(), P(), P())),
            check_vma=False,  # post-gather re-top-k replicated
        )
        def body(qx, qy, lx, ly, lm):
            fd, fi, ov = knn_sparse_scan(
                qx, qy, lx, ly, lm, k=k, tile_capacity=tile_capacity,
                m_blocks=m_blocks, interpret=interpret,
            )
            md, gi = _shard_merge_topk(fd, fi, shard_n, k)
            ov_any = jnp.any(jax.lax.all_gather(ov, SHARD_AXIS))
            if want_count:
                cnt = jax.lax.psum(
                    jnp.sum(lm, dtype=jnp.int64), SHARD_AXIS)
                return md, gi, ov_any, cnt
            return md, gi, ov_any

        return body(qx, qy, x, y, mask)

    return run


def make_knn_fullscan_sharded(mesh):
    """Dense mesh fallback for `make_knn_serve_sharded`'s overflow
    contract: each chip runs the exact `knn_fullscan` over its rows;
    the merge is identical. Per-pair distances are the same f32
    haversine the serial fallback computes, so the overflow path stays
    bit-identical too."""
    from jax.sharding import PartitionSpec as P

    from geomesa_tpu.parallel.mesh import SHARD_AXIS

    d_count = int(mesh.devices.size)

    def run(qx, qy, x, y, mask, k, m_blocks, interpret):
        shard_n = x.shape[0] // d_count

        @functools.partial(
            _shard_map,
            mesh=mesh,
            in_specs=(P(), P(), P(SHARD_AXIS), P(SHARD_AXIS),
                      P(SHARD_AXIS)),
            out_specs=(P(), P()),
            check_vma=False,
        )
        def body(qx, qy, lx, ly, lm):
            fd, fi = knn_fullscan(
                qx, qy, lx, ly, lm, k=k, m_blocks=m_blocks,
                interpret=interpret,
            )
            return _shard_merge_topk(fd, fi, shard_n, k)

        return body(qx, qy, x, y, mask)

    return run


def knn_fullscan_tiled(
    qx: jax.Array,
    qy: jax.Array,
    x: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    k: int,
    m_blocks: int = 64,
    query_tile: int = 256,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """knn_fullscan for arbitrary Q: queries processed in centroid-centered
    tiles of `query_tile` (each tile re-scans the batch — the scan is one
    HBM pass, so wall time scales with ceil(Q/query_tile))."""
    q = qx.shape[0]
    if q <= query_tile:
        return knn_fullscan(qx, qy, x, y, mask, k=k, m_blocks=m_blocks,
                            interpret=interpret)
    pad = (-q) % query_tile
    qxp = jnp.pad(qx, (0, pad), mode="edge")
    qyp = jnp.pad(qy, (0, pad), mode="edge")

    def tile(args):
        tx, ty = args
        return knn_fullscan(tx, ty, x, y, mask, k=k, m_blocks=m_blocks,
                            interpret=interpret)

    fd, fi = jax.lax.map(
        tile, (qxp.reshape(-1, query_tile), qyp.reshape(-1, query_tile))
    )
    return fd.reshape(-1, k)[:q], fi.reshape(-1, k)[:q]
