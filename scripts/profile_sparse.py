"""Sparse-scan kNN on Z-ordered (store-order) data vs dense fullscan."""

from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from geomesa_tpu.engine.knn_scan import knn_fullscan, knn_sparse_scan
from scripts._util import sync, timeit


def morton(x, y):
    qx = np.clip(((x + 180.0) / 360.0 * 65535.0), 0, 65535).astype(np.uint64)
    qy = np.clip(((y + 90.0) / 180.0 * 65535.0), 0, 65535).astype(np.uint64)

    def spread(v):
        v = (v | (v << 16)) & np.uint64(0x0000FFFF0000FFFF)
        v = (v | (v << 8)) & np.uint64(0x00FF00FF00FF00FF)
        v = (v | (v << 4)) & np.uint64(0x0F0F0F0F0F0F0F0F)
        v = (v | (v << 2)) & np.uint64(0x3333333333333333)
        v = (v | (v << 1)) & np.uint64(0x5555555555555555)
        return v

    return spread(qx) | (spread(qy) << np.uint64(1))


def main():
    n = 1 << 26
    q = 256
    k = 10
    rng = np.random.default_rng(42)
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-90, 90, n)
    # store order: Z-sorted (the FS/KV store's physical layout)
    order = np.argsort(morton(x, y))
    x, y = x[order], y[order]
    t = rng.integers(1_590_000_000_000, 1_600_000_000_000, n)
    speed = rng.uniform(0, 30, n)
    qx = rng.uniform(-30, 30, q)
    qy = rng.uniform(30, 60, q)
    BBOX = (-60.0, 20.0, 60.0, 70.0)
    T0, T1 = 1_592_000_000_000, 1_598_000_000_000

    dx = jnp.asarray(x, jnp.float32)
    dy = jnp.asarray(y, jnp.float32)
    dt = jnp.asarray(t, jnp.int64)
    dspeed = jnp.asarray(speed, jnp.float32)
    dqx = jnp.asarray(qx, jnp.float32)
    dqy = jnp.asarray(qy, jnp.float32)
    sync(dspeed)

    mask_np = (
        (x >= BBOX[0]) & (x <= BBOX[2]) & (y >= BBOX[1]) & (y <= BBOX[3])
        & (t > T0) & (t < T1) & (speed > 5.0)
    )
    ntiles = n // 16384
    tiles_hit = (mask_np.reshape(ntiles, -1).any(1)).sum()
    print(f"count {mask_np.sum()}, tiles {tiles_hit}/{ntiles} hit "
          f"({100*tiles_hit/ntiles:.1f}%)", flush=True)
    cap = 1 << int(np.ceil(np.log2(tiles_hit * 1.25)))
    print(f"tile capacity {cap}", flush=True)

    def mk_mask(x, y, t, speed):
        return (
            (x >= BBOX[0]) & (x <= BBOX[2]) & (y >= BBOX[1]) & (y <= BBOX[3])
            & (t > T0) & (t < T1) & (speed > 5.0)
        )

    @jax.jit
    def fused_sparse(x, y, t, speed, qx, qy):
        m = mk_mask(x, y, t, speed)
        cnt = jnp.sum(m.astype(jnp.int32))
        fd, fi, ov = knn_sparse_scan(qx, qy, x, y, m, k=k, tile_capacity=cap)
        return cnt, fd, fi, ov

    @jax.jit
    def fused_dense(x, y, t, speed, qx, qy):
        m = mk_mask(x, y, t, speed)
        cnt = jnp.sum(m.astype(jnp.int32))
        fd, fi = knn_fullscan(qx, qy, x, y, m, k=k)
        return cnt, fd, fi

    print("compiling sparse...", flush=True)
    s = time.perf_counter()
    out = fused_sparse(dx, dy, dt, dspeed, dqx, dqy)
    sync(out[1])
    print(f"  {time.perf_counter()-s:.0f}s; overflow={bool(out[3])}",
          flush=True)
    t1 = timeit(lambda: sync(fused_sparse(dx, dy, dt, dspeed, dqx, dqy)[1]))
    print(f"sparse latency:  {t1*1e3:7.1f} ms "
          f"-> {n/t1/1e6:.0f}M pts/s", flush=True)

    R = 8

    def sustained():
        outs = [fused_sparse(dx, dy, dt, dspeed, dqx, dqy)[1]
                for _ in range(R)]
        for o in outs:
            sync(o)

    ts = timeit(sustained, repeats=3)
    print(f"sparse sustained x{R}: {ts*1e3:7.1f} ms -> "
          f"{R*n/ts/1e6:.0f}M pts/s", flush=True)

    print("compiling dense...", flush=True)
    s = time.perf_counter()
    out = fused_dense(dx, dy, dt, dspeed, dqx, dqy)
    sync(out[1])
    print(f"  {time.perf_counter()-s:.0f}s", flush=True)
    t2 = timeit(lambda: sync(fused_dense(dx, dy, dt, dspeed, dqx, dqy)[1]))
    print(f"dense latency:   {t2*1e3:7.1f} ms "
          f"-> {n/t2/1e6:.0f}M pts/s", flush=True)

    # recall parity vs numpy oracle
    from geomesa_tpu.engine.geodesy import haversine_m_np

    cnt, fd, fi, ov = fused_sparse(dx, dy, dt, dspeed, dqx, dqy)
    got = np.sort(np.asarray(fd), axis=1)
    cx_np, cy_np = x[mask_np], y[mask_np]
    bad = 0
    for i in range(q):
        d = haversine_m_np(qx[i], qy[i], cx_np, cy_np)
        exp = np.sort(d[np.argpartition(d, k - 1)[:k]])
        if not np.allclose(exp, got[i], rtol=1e-4, atol=1.0):
            bad += 1
    print(f"sparse recall parity: {q-bad}/{q} exact; count {int(cnt)} "
          f"vs np {mask_np.sum()}", flush=True)


if __name__ == "__main__":
    main()
