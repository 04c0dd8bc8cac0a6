"""Cell-dictionary density: the store-order-aware heatmap kernel.

Parity role: DensityScan / DensityProcess (SURVEY.md §3.5) at the
north-star scale — config 4's 512x512 heatmap over 10s of millions of
points. The round-2 kernels pay per-point costs that dwarf the HBM
roofline: XLA scatter-add serializes (~1 cycle/point), and the dense MXU
one-hot formulation (`density.density_grid_mxu`) builds [T, H] + [T, W]
one-hots (~3 VPU cycles/point at 512^2 — measured 0.45-0.65 s at 67M).

The insight (same family as the sparse kNN scan): index scans emit rows
in STORE ORDER — the Z curve — so consecutive points are spatially
local, and a 4096-point data tile touches only a HANDFUL of distinct
density cells (~16-64 at config-4 shapes; uniform 67M over 512^2 is
~256 points per cell). Each tile gets a DICTIONARY of its distinct cell
ids, built on device (sort + dedupe, one calibration pass), and the
kernel one-hots points against that narrow dictionary:

  per tile:  match[i, j] = (cell(point_i) == dict[j])     [chunk, capd]
             counts[j] += sum_i match[i, j] * w_i          (VMEM)
  finally:   grid.at[dict].add(counts)                     (one scatter)

capd is the pow2 bucket of the median distinct-cell count (~64), so the
per-point cost is ~capd/1024 lanes * ~3 ops ~ 0.2 VPU cycles — an
HBM-bound kernel. A span-based variant (round-4 first cut) used
base+offset locality instead; measured Morton spans of store tiles run
512-1024 (alignment + world-vs-grid curve mismatch), making its one-hot
as wide as the dense kernel's — the dictionary restores the ~10x.

Exactness: identical contract to `density_grid` (same binning, same
mask/out-of-bounds exclusion). Counts are exact; weighted sums agree
with the scatter path to f32 summation-order noise. Tiles with more
distinct cells than capd and tiles with no matching points are EXCLUDED
from the kernel: empty tiles are pruned outright (the VERDICT r3
tile-pruning item), overflow tiles go to the caller's EXACT scatter
fallback (the bf16 hi/lo MXU fallback of the first cut failed the
weighted cells-parity gate on hardware).

Mosaic notes: the dictionary rides as a (1, 1, capd) VMEM operand
(block == array dims satisfies the lane rule at any capd); out blocks
use the same 3-D idiom; scoped VMEM bounds chunk x capd.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from jax import enable_x64 as _enable_x64
import numpy as np

BBox = Tuple[float, float, float, float]

DATA_TILE = 4096
CHUNK = 1024  # hardware sweep (round 5): 144 ms vs 185 ms at 2048/4096
MAX_CAPD = 512   # beyond this many distinct cells the scatter path wins
BIGCELL = 1 << 30


def _bin_cells(x, y, mask, bbox: BBox, width: int, height: int):
    """Shared binning math: (raster cell id row*W+col i32, in-bounds)."""
    xmin, ymin, xmax, ymax = bbox
    dx = (xmax - xmin) / width
    dy = (ymax - ymin) / height
    col = jnp.floor((x - xmin) / dx).astype(jnp.int32)
    row = jnp.floor((y - ymin) / dy).astype(jnp.int32)
    inb = (col >= 0) & (col < width) & (row >= 0) & (row < height) & mask
    # i32-pinned clip bounds: bare Python ints trace as weak i64 when
    # the interpret-mode kernel trace is deferred past the
    # enable_x64(False) window, and the while-loop lowering rejects it
    col = jnp.clip(col, jnp.int32(0), jnp.int32(width - 1))
    row = jnp.clip(row, jnp.int32(0), jnp.int32(height - 1))
    return row * width + col, inb


class DensityCalib(NamedTuple):
    """Plan from one calibration pass (cacheable across queries, like
    the sparse kNN tile capacity). `dicts` is a DEVICE array."""

    tile_ids: np.ndarray   # [S] tiles the sparse kernel scans
    dicts: object          # [S, capd] i32 device: distinct cells (-1 pad)
    capd: int              # dictionary width (pow2)
    dense_ids: np.ndarray  # tiles with > capd distinct cells -> fallback
    n_tiles: int


@functools.partial(
    jax.jit, static_argnames=("bbox", "width", "height", "data_tile")
)
def _tile_sorted_cells(x, y, mask, bbox: BBox, width: int, height: int,
                       data_tile: int):
    """Per-tile sorted cell ids (+BIGCELL for masked/out rows), first-
    occurrence flags, and distinct counts."""
    n = x.shape[0]
    pad = (-n) % data_tile
    xp = jnp.pad(x.astype(jnp.float32), (0, pad))
    yp = jnp.pad(y.astype(jnp.float32), (0, pad))
    mp = jnp.pad(mask, (0, pad))
    cells, ok = _bin_cells(xp, yp, mp, bbox, width, height)
    nt = cells.shape[0] // data_tile
    zt = jnp.where(ok, cells, BIGCELL).reshape(nt, data_tile)
    s = jnp.sort(zt, axis=1)
    live = s < BIGCELL
    first = jnp.concatenate(
        [live[:, :1],
         (s[:, 1:] != s[:, :-1]) & live[:, 1:]], axis=1)
    return s, first, jnp.sum(first.astype(jnp.int32), axis=1)


@functools.partial(jax.jit, static_argnames=("capd",))
def _tile_dicts(s, first, capd: int):
    """[nt, capd] distinct-cell dictionaries (-1 pads): re-sort with
    duplicates pushed to BIGCELL, take the first capd slots."""
    t = jnp.where(first, s, BIGCELL)
    t2 = jnp.sort(t, axis=1)[:, :capd]
    return jnp.where(t2 >= BIGCELL, -1, t2).astype(jnp.int32)


def calibrate_density(
    x, y, mask, bbox: BBox, width: int, height: int,
    data_tile: int = DATA_TILE, slack: float = 2.0,
) -> DensityCalib:
    """One device sort pass + one small ([n_tiles] i32) fetch: per-tile
    distinct-cell dictionaries under the CURRENT mask. capd is a pow2
    bucket of the median distinct count x slack."""
    s, first, distinct = _tile_sorted_cells(
        x, y, mask, bbox, width, height, data_tile)
    dn = np.asarray(distinct)
    # calibration-plan shapes: the tile list is sized once per
    # (batch, filter) calibration and reused via the returned calib,
    # so compiles track plan builds, not traffic
    # gt: waive GT28
    nt = len(dn)
    ids = np.nonzero(dn > 0)[0]
    if len(ids) == 0:
        return DensityCalib(
            np.zeros(0, np.int32), jnp.zeros((0, 8), jnp.int32), 8,
            np.zeros(0, np.int32), nt,
        )
    capd = int(min(MAX_CAPD, max(
        8, 1 << int(np.ceil(np.log2(max(
            float(np.median(dn[ids])) * slack, 2.0))))
    )))
    fits = dn[ids] <= capd
    sel = ids[fits].astype(np.int32)
    dicts = jnp.take(_tile_dicts(s, first, capd), jnp.asarray(sel), axis=0)
    return DensityCalib(
        sel, dicts, capd, ids[~fits].astype(np.int32), nt,
    )


def _make_kernel(data_tile: int, chunk: int, capd: int, bbox: BBox,
                 width: int, height: int, tpp: int):
    """tpp data tiles folded per program (each a separate scalar-indexed
    operand triple, the pip-kernel e_per idiom): at bench scale the
    one-tile-per-program grid paid ~16k program launches of fixed
    overhead (~33 ms) against ~6 ms of VPU work — tiles-per-program
    amortizes it tpp-fold. The filter mask arrives pre-folded into the
    weights (masked-out rows carry w=0), saving one operand array per
    tile and a full HBM pass over the mask."""

    def _kernel(ids_ref, dict_ref, *refs):
        out_ref = refs[-1]
        rows = []
        for e in range(tpp):
            x_ref, y_ref, w_ref = refs[3 * e: 3 * e + 3]
            drow = dict_ref[0, e, :].reshape(1, capd)
            acc = jnp.zeros((1, capd), jnp.float32)
            for s in range(data_tile // chunk):
                sl = slice(s * chunk, (s + 1) * chunk)
                cells, ok = _bin_cells(
                    x_ref[0, sl], y_ref[0, sl], True,
                    bbox, width, height,
                )
                # out-of-bounds zeroing folds into the f32 weights, NOT
                # a bool reshape: Mosaic rejects minor-dim insertion on i1.
                # f32-pinned zeros: bare 0.0 traces as weak f64 when the
                # interpret-mode kernel trace runs under global x64 mode
                zero = jnp.zeros((), jnp.float32)
                lw = jnp.where(ok, w_ref[0, sl], zero).reshape(chunk, 1)
                match = cells.reshape(chunk, 1) == drow
                acc = acc + jnp.sum(
                    jnp.where(match, lw, zero), axis=0,
                ).reshape(1, capd)
            rows.append(acc)
        out_ref[...] = jnp.concatenate(rows, axis=0).reshape(out_ref.shape)

    return _kernel


TILES_PER_PROGRAM = 4


@functools.partial(
    jax.jit,
    static_argnames=(
        "capd", "bbox", "width", "height", "data_tile", "chunk",
        "interpret", "tpp",
    ),
)
def _zsparse_call(
    x, y, lw, tile_ids, dicts,
    capd: int, bbox: BBox, width: int, height: int,
    data_tile: int, chunk: int, interpret: bool,
    tpp: int = TILES_PER_PROGRAM,
):
    """`lw` carries the mask pre-folded (w where mask else 0). VMEM
    budget at tpp=4, capd<=512: 12 data blocks x 128 KB (sublane-padded)
    x 2 (double-buffer) + the padded out stack block — comfortably
    inside the 16 MB scoped limit (tpp=8 with a separate mask operand
    measured 30.6 MB and failed to compile)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = x.shape[0]
    s0 = tile_ids.shape[0]
    # per-program VMEM scales with tpp * capd (data blocks + the
    # [chunk, capd] match transients): capd=512 at tpp=4 measured 16.35M
    # scoped and failed to compile — shrink tpp as the dictionary widens
    tpp = max(1, min(tpp, (64 * TILES_PER_PROGRAM) // max(capd, 64)))
    tpp = min(tpp, s0)
    pad = (-s0) % tpp
    if pad:
        # pad rows scan tile 0 against an all(-1) dictionary: nothing
        # matches, zeros fold into the sink slot
        tile_ids = jnp.concatenate(
            [tile_ids, jnp.zeros(pad, tile_ids.dtype)])
        dicts = jnp.concatenate(
            [dicts, jnp.full((pad, capd), -1, dicts.dtype)])
    s = s0 + pad
    xr = x.astype(jnp.float32).reshape(1, n)
    yr = y.astype(jnp.float32).reshape(1, n)
    wr = lw.astype(jnp.float32).reshape(1, n)
    dr = dicts.reshape(s // tpp, tpp, capd)

    def data_block(e):
        return pl.BlockSpec(
            (1, data_tile), lambda p, ids, e=e: (0, ids[p * tpp + e]))

    dict_block = pl.BlockSpec((1, tpp, capd), lambda p, ids: (p, 0, 0))
    data_specs = []
    data_args = []
    for e in range(tpp):
        data_specs.extend([data_block(e)] * 3)
        data_args.extend([xr, yr, wr])
    with _enable_x64(False):
        counts = pl.pallas_call(
            _make_kernel(data_tile, chunk, capd, bbox, width, height, tpp),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(s // tpp,),
                in_specs=[dict_block] + data_specs,
                out_specs=pl.BlockSpec(
                    (1, tpp, capd), lambda p, ids: (p, 0, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((s // tpp, tpp, capd),
                                           jnp.float32),
            interpret=interpret,
        )(tile_ids.astype(jnp.int32), dr, *data_args)
    return counts.reshape(s, capd)[:s0]


@functools.partial(jax.jit, static_argnames=("width", "height"))
def _fold_counts(counts, dicts, width: int, height: int):
    """Scatter per-tile count rows into the raster grid via their cell
    dictionaries (-1 pads route to a sink slot)."""
    sink = width * height
    idx = jnp.where(dicts < 0, sink, dicts)
    grid = jnp.zeros(sink + 1, jnp.float32)
    grid = grid.at[idx.reshape(-1)].add(counts.reshape(-1))
    return grid[:sink].reshape(height, width)


@functools.partial(
    jax.jit, static_argnames=("bbox", "width", "height")
)
def _expected_mass(x, y, w, mask, bbox: BBox, width: int, height: int):
    _, ok = _bin_cells(x, y, mask, bbox, width, height)
    # deliberate f64 accumulation: the mass check is the recall oracle
    # accumulation-only upcast: summing f32 weights in f64 bounds the
    # reduction error of the oracle itself; no claim is made about
    # pre-cast precision, so the exactness-leak rule does not apply
    # gt: waive GT29
    return jnp.sum(jnp.where(ok, w.astype(jnp.float64), 0.0))  # gt: f64-refine


def density_zsparse(
    x: jax.Array,
    y: jax.Array,
    weights: jax.Array,
    mask: jax.Array,
    bbox: BBox,
    width: int,
    height: int,
    calib: Optional[DensityCalib] = None,
    data_tile: int = DATA_TILE,
    interpret: bool = False,
    check_stale: bool = True,
    stale_exact: bool = False,
) -> Tuple[jax.Array, DensityCalib]:
    """Store-order density grid (see module docstring). Returns
    ([height, width] f32 grid, calib) — pass `calib` back in on repeat
    queries over the same batch+filter to skip the calibration pass.
    Exact contract of `density.density_grid` for any input order; the
    sparse win requires store (Z) order, the fallback keeps it correct
    otherwise.

    A REUSED calib is validated (`check_stale`): unlike the kNN tile
    capacity, a stale density plan is a silent correctness failure (a
    point in a tile pruned under the OLD mask, or whose cell is missing
    from the tile's cached dictionary, would vanish from the grid), so
    the grid's total mass is checked against the mask's expected mass
    and a mismatch triggers automatic recalibration. Callers looping
    the IDENTICAL query (mask unchanged) may pass check_stale=False to
    skip the extra device reduction + fetch.

    With `stale_exact` (unweighted grids: cell values are small-integer
    counts, exact in f32), the mass check runs at atol=0.5 — ONE dropped
    point triggers recalibration. The default relative tolerance only
    bounds f32 summation noise for WEIGHTED grids; a sub-noise deficit
    (a handful of points against tens of millions) can pass it, so
    callers caching calibs across queries must key the cache on the
    FILTER as well as the arrays (see plan.runner._zsparse_grid)."""
    from geomesa_tpu.engine.density import density_grid

    reused_calib = calib is not None
    n = x.shape[0]
    pad = (-n) % data_tile
    xp = jnp.pad(x.astype(jnp.float32), (0, pad))
    yp = jnp.pad(y.astype(jnp.float32), (0, pad))
    wp = jnp.pad(weights.astype(jnp.float32), (0, pad))
    mp = jnp.pad(mask, (0, pad))
    if calib is None:
        calib = calibrate_density(
            xp, yp, mp, bbox, width, height, data_tile=data_tile
        )

    grid = jnp.zeros((height, width), jnp.float32)
    lwp = jnp.where(mp, wp, 0.0)  # mask pre-folded (one fused pass)
    if len(calib.tile_ids):
        # chunk the tile list so one call's output + dictionary operand
        # stay small (XLA may place a pallas output in VMEM; a full
        # [S, 1, cap] array blew the 16 MB scoped limit at bench scale)
        maxs = max(256, (1 << 19) // max(calib.capd, 1))
        S = len(calib.tile_ids)
        for c0 in range(0, S, maxs):
            c1 = min(c0 + maxs, S)
            ids_c = calib.tile_ids[c0:c1]
            dict_c = calib.dicts[c0:c1]
            # chunk pad: every chunk is padded up to the fixed `maxs`,
            # so the kernel sees one stable shape per calib plan (the
            # len() only sizes the pad amount)
            # gt: waive GT28
            pad_c = maxs - len(ids_c) if S > maxs else 0
            if pad_c:  # stable shapes across chunks (one compile)
                ids_c = np.concatenate(
                    [ids_c, np.full(pad_c, ids_c[0], ids_c.dtype)])
                dict_c = jnp.concatenate([
                    dict_c,
                    jnp.full((pad_c, calib.capd), -1, jnp.int32),
                ])
                # padding rows re-scan a real tile against an all-pad
                # dictionary: nothing matches, zeros fold into the sink
            counts = _zsparse_call(
                xp, yp, lwp,
                jnp.asarray(ids_c), jnp.asarray(dict_c),
                capd=calib.capd, bbox=tuple(bbox), width=width,
                height=height,
                data_tile=data_tile, chunk=min(CHUNK, data_tile),
                interpret=interpret,
            )
            grid = grid + _fold_counts(
                counts, dict_c, width=width, height=height)
    if len(calib.dense_ids):
        # overflow tiles (unsorted input / cell-dense regions): block-
        # gather their points and take the EXACT scatter path (the bf16
        # hi/lo MXU fallback failed the weighted cells-parity gate)
        ids = jnp.asarray(calib.dense_ids)
        gx = jnp.take(xp.reshape(-1, data_tile), ids, axis=0).reshape(-1)
        gy = jnp.take(yp.reshape(-1, data_tile), ids, axis=0).reshape(-1)
        gw = jnp.take(wp.reshape(-1, data_tile), ids, axis=0).reshape(-1)
        gm = jnp.take(mp.reshape(-1, data_tile), ids, axis=0).reshape(-1)
        grid = grid + density_grid(gx, gy, gw, gm, tuple(bbox),
                                   width, height)
    if reused_calib and check_stale:
        expected = float(_expected_mass(
            xp, yp, wp, mp, tuple(bbox), width, height))
        # accumulation-only upcast: the f32 grid is summed in f64 so
        # the mass comparison is not noise-limited; it feeds a
        # tolerance check, not an exact-f64 answer
        # gt: waive GT29
        got = float(np.asarray(grid, np.float64).sum())
        rtol, atol = (0.0, 0.5) if stale_exact else (1e-5, 1e-3)
        if not np.isclose(got, expected, rtol=rtol, atol=atol):
            # the cached plan no longer covers this mask: recalibrate
            return density_zsparse(
                x, y, weights, mask, bbox, width, height, calib=None,
                data_tile=data_tile, interpret=interpret,
            )
    return grid, calib


def density_zsparse_sharded(
    mesh,
    x: jax.Array,
    y: jax.Array,
    weights: jax.Array,
    mask: jax.Array,
    bbox: BBox,
    width: int,
    height: int,
    data_tile: int = DATA_TILE,
    interpret: bool = False,
):
    """Data-parallel cell-dictionary density over a device mesh.

    One GLOBAL calibration pass (per-tile dictionaries are a property of
    the row layout, not of the shard cut), partitioned by shard — rows
    are split contiguously and the shard size is a tile multiple, so a
    data tile never crosses a shard boundary. Each shard runs the same
    Pallas kernel over its local tiles (lists padded to a common length
    with all(-1) dictionaries — pad rows match nothing and fold zeros),
    overflow tiles take the exact per-shard scatter fallback, and the
    per-shard grids merge with one psum — the C25 reduction-tree shape
    (SURVEY.md:318-329) on XLA collectives.

    Returns the REPLICATED [height, width] grid (same contract as
    density_sharded)."""
    import jax.lax as lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    from geomesa_tpu.engine.density import density_grid
    from geomesa_tpu.parallel.mesh import SHARD_AXIS

    D = int(np.prod(mesh.devices.shape))
    n = int(x.shape[0])
    per = n // D
    if n % D or per % data_tile:
        raise ValueError(
            f"n={n} must split into {D} shards of data_tile={data_tile} "
            "multiples (pad the batch; the planner's pow2 padding does)"
        )
    calib = calibrate_density(
        x, y, mask, bbox, width, height, data_tile=data_tile)
    tpd = per // data_tile

    def _partition(global_ids, payload=None, fill=0):
        """[n_sel] global tile ids -> ([D, S] local ids, [D, S] valid,
        optionally [D, S, ...] payload) padded to the max shard count."""
        shard_of = global_ids // tpd
        counts = np.bincount(shard_of, minlength=D)
        S = max(int(counts.max()), 1)
        ids = np.full((D, S), fill, np.int32)
        valid = np.zeros((D, S), bool)
        pay = None
        if payload is not None:
            pay = np.full((D, S) + payload.shape[1:], -1, payload.dtype)
        for d in range(D):
            sel = np.nonzero(shard_of == d)[0]
            ids[d, : len(sel)] = global_ids[sel] - d * tpd
            valid[d, : len(sel)] = True
            if payload is not None:
                pay[d, : len(sel)] = payload[sel]
        return ids, valid, pay

    sp_ids, _, sp_dicts = _partition(
        calib.tile_ids.astype(np.int64), np.asarray(calib.dicts))
    have_dense = len(calib.dense_ids) > 0
    if have_dense:
        dn_ids, dn_valid, _ = _partition(calib.dense_ids.astype(np.int64))
    else:
        dn_ids = np.zeros((D, 1), np.int32)
        dn_valid = np.zeros((D, 1), bool)

    capd = calib.capd
    bbox = tuple(bbox)

    def shard_fn(xl, yl, wl, ml, idsl, dictsl, didl, dvall):
        # sharded [D, ...] operands arrive with a leading length-1 dim
        idsl = idsl.reshape(-1)
        dictsl = dictsl.reshape(-1, capd)
        didl = didl.reshape(-1)
        dvall = dvall.reshape(-1)
        lwl = jnp.where(ml, wl, 0.0)  # mask pre-folded (driver idiom)
        # chunk the tile list exactly like the single-device driver: a
        # full [S, 1, capd] pallas output may land in VMEM and blew the
        # 16 MB scoped limit at bench scale (review finding — the mesh
        # path must survive the scale it exists for)
        S = int(idsl.shape[0])
        maxs = max(256, (1 << 19) // max(capd, 1))
        grid = jnp.zeros((height, width), jnp.float32)
        for c0 in range(0, S, maxs):
            c1 = min(c0 + maxs, S)
            counts = _zsparse_call(
                xl, yl, lwl, idsl[c0:c1], dictsl[c0:c1],
                capd=capd, bbox=bbox, width=width, height=height,
                data_tile=data_tile, chunk=min(CHUNK, data_tile),
                interpret=interpret,
            )
            grid = grid + _fold_counts(
                counts, dictsl[c0:c1], width=width, height=height)
        if have_dense:
            gx = jnp.take(xl.reshape(tpd, data_tile), didl, axis=0)
            gy = jnp.take(yl.reshape(tpd, data_tile), didl, axis=0)
            gw = jnp.take(wl.reshape(tpd, data_tile), didl, axis=0)
            gm = jnp.take(ml.reshape(tpd, data_tile), didl, axis=0)
            gm = gm & dvall[:, None]
            grid = grid + density_grid(
                gx.reshape(-1), gy.reshape(-1), gw.reshape(-1),
                gm.reshape(-1), bbox, width, height,
            )
        return lax.psum(grid, SHARD_AXIS)

    f = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
            P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
        ),
        out_specs=P(),
        check_vma=False,  # pallas output vma; psum replicates (knn idiom)
    )
    return f(
        x.astype(jnp.float32), y.astype(jnp.float32),
        weights.astype(jnp.float32), mask,
        jnp.asarray(sp_ids), jnp.asarray(sp_dicts),
        jnp.asarray(dn_ids), jnp.asarray(dn_valid),
    )
