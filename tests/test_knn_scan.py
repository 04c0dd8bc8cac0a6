"""Fused-scan kNN kernel tests (Pallas interpret mode on CPU; the same
code path compiles via Mosaic on TPU — measured there at 570M pts/s
sparse / 259M dense on the 67M-point config-3 shape).

Parity oracle: NumPy f64 haversine + argpartition over the masked rows
(tests/reference_engine.py style), the same oracle the bench gates on.
"""

import functools

import numpy as np
import pytest

import jax.numpy as jnp

from geomesa_tpu.engine.geodesy import haversine_m_np
from geomesa_tpu.engine.knn_scan import (
    DATA_TILE, chord_blockmin, knn_fullscan, knn_fullscan_tiled,
    knn_ring_scan, knn_sparse_scan, knn_tile_summary)

# tiny tiles: interpret mode executes the grid serially in Python — the
# TPU-targeted tile sizes would take minutes per call on CPU
TINY = dict(blk=256, data_tile=2048)


def oracle(qx, qy, x, y, mask, k):
    out = np.empty((len(qx), k))
    cx, cy = x[mask], y[mask]
    for i in range(len(qx)):
        d = haversine_m_np(qx[i], qy[i], cx, cy)
        if len(d) >= k:
            out[i] = np.sort(d[np.argpartition(d, k - 1)[:k]])
        else:
            out[i, : len(d)] = np.sort(d)
            out[i, len(d):] = np.inf
    return out


def make(n, q, seed=7, sorted_x=False, sel=0.4):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-180, 180, n)
    if sorted_x:
        x = np.sort(x)
    y = rng.uniform(-90, 90, n)
    mask = rng.random(n) < sel
    qx = rng.uniform(-30, 30, q)
    qy = rng.uniform(-60, 60, q)
    dev = [jnp.asarray(a, jnp.float32) for a in (qx, qy, x, y)]
    return qx, qy, x, y, mask, dev + [jnp.asarray(mask)]


class TestFullscan:
    def test_parity_random_mask(self):
        qx, qy, x, y, mask, dev = make(6000, 24)
        fd, fi = knn_fullscan(*dev, k=5, m_blocks=8, interpret=True, **TINY)
        exp = oracle(qx, qy, x, y, mask, 5)
        np.testing.assert_allclose(
            np.sort(np.asarray(fd), 1), exp, rtol=1e-4, atol=1.0)
        # returned indices are real matches whose distances reproduce fd
        idx = np.asarray(fi)
        for i in range(5):
            dd = haversine_m_np(qx[i], qy[i], x[idx[i]], y[idx[i]])
            np.testing.assert_allclose(
                np.sort(dd), np.sort(np.asarray(fd)[i]), rtol=1e-4, atol=1.0)
            assert mask[idx[i]].all()

    def test_fewer_matches_than_k(self):
        qx, qy, x, y, _, dev = make(4096, 8)
        mask = np.zeros(4096, bool)
        mask[[5, 99, 3000]] = True
        dev[4] = jnp.asarray(mask)
        fd, fi = knn_fullscan(*dev, k=6, m_blocks=8, interpret=True, **TINY)
        fd = np.asarray(fd)
        assert np.isfinite(fd[:, :3]).all() and np.isinf(fd[:, 3:]).all()
        assert mask[np.asarray(fi)[:, :3]].all()

    def test_m_blocks_contract(self):
        _, _, _, _, _, dev = make(2048, 4)
        with pytest.raises(ValueError, match="m_blocks"):
            knn_fullscan(*dev, k=9, m_blocks=8, interpret=True, **TINY)

    def test_query_tiling(self):
        qx, qy, x, y, mask, dev = make(4096, 40)
        fd, _ = knn_fullscan_tiled(
            *dev, k=3, m_blocks=4, query_tile=16, interpret=True)
        exp = oracle(qx, qy, x, y, mask, 3)
        np.testing.assert_allclose(
            np.sort(np.asarray(fd), 1), exp, rtol=1e-4, atol=1.0)

    def test_blockmin_matches_dense_key(self):
        rng = np.random.default_rng(3)
        n, q = 2048, 8
        x = rng.uniform(-180, 180, n).astype(np.float32)
        y = rng.uniform(-90, 90, n).astype(np.float32)
        mf = (rng.random(n) < 0.5).astype(np.float32)
        qx = rng.uniform(-30, 30, q).astype(np.float32)
        qy = rng.uniform(30, 60, q).astype(np.float32)
        minima, c = chord_blockmin(
            jnp.asarray(qx), jnp.asarray(qy), jnp.asarray(x), jnp.asarray(y),
            jnp.asarray(mf), blk=256, data_tile=2048, interpret=True)

        def unit3(lon, lat):
            rl, rt = np.radians(lon), np.radians(lat)
            return np.stack([np.cos(rt) * np.cos(rl),
                             np.cos(rt) * np.sin(rl), np.sin(rt)], -1)

        qu = unit3(qx, qy).astype(np.float32)
        cc = qu.mean(0)
        dc = unit3(x, y).astype(np.float32) - cc
        nd = (dc * dc).sum(1) + (1 - mf) * 1e9
        key = nd[None, :] - 2 * ((qu - cc) @ dc.T)
        exp = key.reshape(q, -1, 256).min(-1)
        got = np.asarray(minima)
        # f32 association-order noise only
        assert np.abs(got - exp).max() / np.abs(exp).max() < 1e-2


class TestSparseScan:
    def test_parity_and_no_overflow_on_sorted(self):
        qx, qy, x, y, _, dev = make(16384, 12, sorted_x=True)
        mask = (x > -60) & (x < 60)
        dev[4] = jnp.asarray(mask)
        fd, fi, ov = knn_sparse_scan(
            *dev, k=5, tile_capacity=8, m_blocks=8, interpret=True, **TINY)
        assert not bool(ov)
        exp = oracle(qx, qy, x, y, mask, 5)
        np.testing.assert_allclose(
            np.sort(np.asarray(fd), 1), exp, rtol=1e-4, atol=1.0)

    def test_overflow_flags_capacity_breach(self):
        _, _, x, y, _, dev = make(16384, 4)
        dev[4] = jnp.asarray(np.ones(16384, bool))
        _, _, ov = knn_sparse_scan(
            *dev, k=3, tile_capacity=4, m_blocks=8, interpret=True, **TINY)
        assert bool(ov)

    def test_empty_mask(self):
        qx, qy, x, y, _, dev = make(4096, 4)
        dev[4] = jnp.asarray(np.zeros(4096, bool))
        fd, _, ov = knn_sparse_scan(
            *dev, k=3, tile_capacity=4, m_blocks=8, interpret=True, **TINY)
        assert not bool(ov)
        assert np.isinf(np.asarray(fd)).all()

    def test_matches_only_in_last_tile(self):
        # selection order: tile ids must map back to ORIGINAL lanes
        qx, qy, x, y, _, dev = make(8192, 6)
        mask = np.zeros(8192, bool)
        mask[-50:] = True
        dev[4] = jnp.asarray(mask)
        fd, fi, ov = knn_sparse_scan(
            *dev, k=4, tile_capacity=2, m_blocks=8, interpret=True, **TINY)
        assert not bool(ov)
        exp = oracle(qx, qy, x, y, mask, 4)
        np.testing.assert_allclose(
            np.sort(np.asarray(fd), 1), exp, rtol=1e-4, atol=1.0)
        assert (np.asarray(fi) >= 8192 - 50).all()


def test_sparse_dead_slots_never_duplicate_tile0():
    # capacity-padding programs alias data tile 0; with sparse matches
    # (fewer real blocks than m_blocks) their PENALTY minima used to win
    # selection and duplicate tile-0 lanes in the refine pool
    rng = np.random.default_rng(13)
    n, q, k = 8192, 6, 4
    x = np.sort(rng.uniform(-180, 180, n))
    y = rng.uniform(-90, 90, n)
    mask = np.zeros(n, bool)
    mask[:6] = True  # all matches in tile 0, fewer than k*blk
    qx = rng.uniform(-30, 30, q)
    qy = rng.uniform(-60, 60, q)
    dev = [jnp.asarray(a, jnp.float32) for a in (qx, qy, x, y)]
    fd, fi, ov = knn_sparse_scan(
        *dev, jnp.asarray(mask), k=k, tile_capacity=8, m_blocks=8,
        interpret=True, **TINY)
    assert not bool(ov)
    fd = np.asarray(fd)
    fi = np.asarray(fi)
    for i in range(q):
        fin = np.isfinite(fd[i])
        assert fin.sum() == k  # 6 matches exist, k=4 all fillable
        # no duplicated neighbor indices among finite results
        assert len(set(fi[i][fin].tolist())) == int(fin.sum())
    exp = oracle(qx, qy, x, y, mask, k)
    np.testing.assert_allclose(
        np.sort(fd, 1), exp, rtol=1e-4, atol=1.0)


def _ring_scan(k, m_blocks):
    import jax

    return jax.jit(functools.partial(
        knn_ring_scan, k=k, m_blocks=m_blocks, interpret=True))


def _ring_mask(case, n):
    mask = np.zeros(n, bool)
    if case == "one_live_tile":
        mask[2 * DATA_TILE + 100: 2 * DATA_TILE + 900] = True
    elif case in ("every_tile_live", "overflow"):
        mask[::97] = True
    elif case == "unaligned":
        mask[np.random.default_rng(5).random(n) < 0.3] = True
    return mask


class TestArmTimeTiles:
    """The ring program's route: `knn_tile_summary` once, then the scan
    half alone (`knn_ring_scan`), at the real tile sizes."""

    @pytest.mark.parametrize("case,n,cap", [
        ("empty", 4 * DATA_TILE, 4),
        ("one_live_tile", 4 * DATA_TILE, 4),
        ("every_tile_live", 4 * DATA_TILE, 4),
        ("overflow", 4 * DATA_TILE, 2),
        ("unaligned", 3 * DATA_TILE + 1000, 4),
    ])
    def test_ring_route_bit_identical_to_sparse_scan(self, case, n, cap):
        _, _, _, _, _, dev = make(n, 4, seed=23)
        mask = jnp.asarray(_ring_mask(case, n))
        fd, fi, ov = knn_sparse_scan(
            *dev[:4], mask, k=5, tile_capacity=cap, m_blocks=8,
            interpret=True)
        summary = knn_tile_summary(mask, tile_capacity=cap)
        rd, ri, rov = _ring_scan(5, 8)(*dev[:4], *summary)
        np.testing.assert_array_equal(np.asarray(rd), np.asarray(fd))
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(fi))
        assert bool(rov) == bool(ov) == (case == "overflow")
        maskf, tile_ids, n_sel, _ = summary
        assert maskf.shape[0] % DATA_TILE == 0
        live = np.flatnonzero(np.asarray(maskf).reshape(
            -1, DATA_TILE).max(1) > 0)
        assert int(n_sel) == len(live)
        np.testing.assert_array_equal(
            np.asarray(tile_ids)[:min(len(live), cap)], live[:cap])
        # the ring's overflow fallback scans the padded f32 mask
        dd, di = knn_fullscan(*dev[:4], maskf, k=5, m_blocks=8,
                              interpret=True)
        bd, bi = knn_fullscan(*dev[:4], mask, k=5, m_blocks=8,
                              interpret=True)
        np.testing.assert_array_equal(np.asarray(dd), np.asarray(bd))
        np.testing.assert_array_equal(np.asarray(di), np.asarray(bi))

    def test_ring_body_makes_no_pass_over_the_rows(self):
        import jax
        from jax.extend.core import ClosedJaxpr, Jaxpr

        n = 4 * DATA_TILE
        _, _, _, _, mask, dev = make(n, 4, seed=29)
        summary = knn_tile_summary(jnp.asarray(mask), tile_capacity=4)
        jaxpr = jax.make_jaxpr(functools.partial(
            knn_ring_scan, k=5, m_blocks=8, interpret=True))(
                *dev[:4], *summary)
        seen = []

        def walk(jx):
            for eqn in jx.eqns:
                if eqn.primitive.name in (
                        "reduce_max", "convert_element_type", "gt"):
                    seen.extend(
                        (eqn.primitive.name, v.aval.shape)
                        for v in eqn.invars
                        if getattr(v.aval, "size", 0) >= n)
                for p in eqn.params.values():
                    for sub in (p if isinstance(p, (tuple, list))
                                else (p,)):
                        if isinstance(sub, ClosedJaxpr):
                            walk(sub.jaxpr)
                        elif isinstance(sub, Jaxpr):
                            walk(sub)

        walk(jaxpr.jaxpr)
        assert seen == []


class TestKnnExactRefine:
    # round 5 (VERDICT r4 task 10): f64 re-ranking at the k-th boundary
    # with a miss-impossible certificate

    def test_engineered_f32_ties_rerank_exactly(self):
        from geomesa_tpu.engine.geodesy import haversine_m_np
        from geomesa_tpu.engine.knn_scan import (
            knn_exact_refine, knn_sparse_auto)

        rng = np.random.default_rng(41)
        n, k, pad = 1 << 12, 5, 8
        qx, qy = np.array([10.0]), np.array([45.0])
        # the k-th boundary is a TIE CLUSTER that fits inside the pad:
        # 3 clearly-closer points (~50 km, distinct) + 8 points along ONE
        # bearing at ~71 km spaced ~1e-10 deg (~10 um) — far below f32
        # resolution, so the f32 kernel genuinely cannot order them
        # (review finding: a random-angle shell spread the distances by
        # 190 m - 2 km and never created a tie). The true top-5 = the 3
        # close + the f64-smallest 2 of the tied 8; only the f64 re-rank
        # can pick those 2, and the certificate holds because the whole
        # cluster fits within k' = k + pad.
        rr = 0.9 + np.arange(8) * 1e-10
        x = np.concatenate([
            qx[0] + np.array([0.63, 0.64, 0.65]),
            qx[0] + rr,
            rng.uniform(30, 60, n - 11),  # far background
        ])
        y = np.concatenate([
            np.full(11, qy[0]),
            rng.uniform(-60, -30, n - 11),
        ])
        mask = np.ones(n, bool)
        # the engineered tie cluster really is f32-indistinguishable
        d32 = haversine_m_np(qx[0], qy[0], x[3:11], y[3:11]).astype(np.float32)
        assert len(np.unique(d32)) < 8
        fd, fi, cap = knn_sparse_auto(
            jnp.asarray(qx, jnp.float32), jnp.asarray(qy, jnp.float32),
            jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32),
            jnp.asarray(mask), k=k + pad, interpret=True)
        d64, idx, cert = knn_exact_refine(qx, qy, x, y, fd, fi, k)
        exp_all = haversine_m_np(qx[0], qy[0], x, y)
        exp = np.sort(exp_all)[:k]
        # EXACT equality: both sides are the same f64 formula over the
        # same original coordinates
        np.testing.assert_array_equal(d64[0], exp)
        assert bool(cert[0])
        # the refined set is the true index set (distances here are
        # distinct in f64 by construction)
        assert set(idx[0].tolist()) == set(np.argsort(exp_all)[:k].tolist())

    def test_antipodal_boundary_decertifies(self):
        # near the antipode the f32 haversine error reaches km scale
        # (asin amplification); the certificate must refuse there even
        # with a comfortable-looking f32 margin (review finding: a flat
        # 4 m + 1e-5*d model falsely certified this regime)
        from geomesa_tpu.engine.knn_scan import (
            knn_exact_refine, knn_f32_err_m)

        assert knn_f32_err_m(100e3) < 10.0           # mid-range: meters
        assert knn_f32_err_m(19.9e6) > 2_000.0       # antipodal: km scale
        qx, qy = np.array([0.0]), np.array([0.0])
        # candidates ~100 km short of the antipode, 500 m apart in f64
        x = 179.0 + np.arange(64) * 0.005
        y = np.full(64, 0.5)
        from geomesa_tpu.engine.geodesy import haversine_m_np

        d_all = haversine_m_np(qx[0], qy[0], x, y)
        o = np.argsort(d_all)[:8]
        fd = d_all[o].astype(np.float32)[None]
        fi = o[None]
        d64, idx, cert = knn_exact_refine(qx, qy, x, y, fd, fi, k=5)
        assert not bool(cert[0])  # 1.5 km margin < km-scale f32 error

    def test_uncertified_when_pad_is_all_ties(self):
        from geomesa_tpu.engine.knn_scan import knn_exact_refine

        # every candidate within sub-resolution of the k-th boundary and
        # beyond the pad: the certificate must refuse
        qx, qy = np.array([0.0]), np.array([0.0])
        x = np.full(64, 1.0)
        y = np.zeros(64)
        fd = np.full((1, 8), np.float32(111194.9), np.float32)
        fi = np.arange(8, dtype=np.int64)[None]
        d64, idx, cert = knn_exact_refine(qx, qy, x, y, fd, fi, k=5)
        assert not bool(cert[0])

    def test_certified_short_result(self):
        from geomesa_tpu.engine.knn_scan import knn_exact_refine

        # fewer matches than k': nothing was cut off -> certified
        qx, qy = np.array([0.0]), np.array([0.0])
        x = np.array([1.0, 2.0, 3.0])
        y = np.zeros(3)
        fd = np.array([[111000.0, 222000.0, 333000.0, np.inf, np.inf,
                        np.inf, np.inf, np.inf]], np.float32)
        fi = np.array([[0, 1, 2, 0, 0, 0, 0, 0]], np.int64)
        d64, idx, cert = knn_exact_refine(qx, qy, x, y, fd, fi, k=5)
        assert bool(cert[0])
        assert np.isinf(d64[0, 3:]).all()
