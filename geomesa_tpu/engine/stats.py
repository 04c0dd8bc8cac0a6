"""Masked statistical reductions — the device side of StatsScan.

Parity: geomesa-index-api StatsScan + the Stat sketch evaluation hot path
(geomesa-utils stats) [upstream, unverified]. Each function is a pure masked
reduction over device columns producing small arrays that merge across shards
with psum/min/max — the collective analog of the reference's mergeable
sketches streaming from tablet servers. Host-side mergeable sketch *objects*
(Stat DSL, serialization) live in geomesa_tpu.stats; these kernels feed them.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P

from geomesa_tpu.parallel.mesh import SHARD_AXIS


@jax.jit
def masked_count(mask: jax.Array) -> jax.Array:
    return jnp.sum(mask.astype(jnp.int64))


@jax.jit
def masked_minmax(v: jax.Array, mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    big = jnp.asarray(jnp.inf, jnp.float64)  # gt: f64-refine
    vf = v.astype(jnp.float64)  # gt: f64-refine
    return (
        jnp.min(jnp.where(mask, vf, big)),
        jnp.max(jnp.where(mask, vf, -big)),
    )


@jax.jit
def masked_moments(v: jax.Array, mask: jax.Array):
    """(count, sum, sum-of-squares) in f64 — exact merge across shards by
    adding components (DescriptiveStats parity)."""
    vf = jnp.where(mask, v.astype(jnp.float64), 0.0)  # gt: f64-refine
    return (
        jnp.sum(mask.astype(jnp.int64)),
        jnp.sum(vf),
        jnp.sum(vf * vf),
    )


@functools.partial(jax.jit, static_argnames=("bins",))
def masked_histogram(
    v: jax.Array, mask: jax.Array, lo: float, hi: float, bins: int
) -> jax.Array:
    """Fixed-width binned histogram (Histogram stat parity). Values outside
    [lo, hi] clamp into the end bins, as the reference's Histogram does."""
    vf = v.astype(jnp.float32)
    idx = jnp.floor((vf - lo) / ((hi - lo) / bins)).astype(jnp.int32)
    idx = jnp.clip(idx, 0, bins - 1)
    w = mask.astype(jnp.int32)
    return jnp.zeros(bins, jnp.int32).at[idx].add(w)


@functools.partial(jax.jit, static_argnames=("vocab_size",))
def masked_value_counts(codes: jax.Array, mask: jax.Array, vocab_size: int) -> jax.Array:
    """Per-dictionary-code counts (Frequency/TopK/Enumeration parity feed).
    Null codes (-1) and codes beyond the vocab are dropped."""
    valid = mask & (codes >= 0) & (codes < vocab_size)
    idx = jnp.clip(codes, 0, max(vocab_size - 1, 0))
    w = valid.astype(jnp.int32)
    return jnp.zeros(max(vocab_size, 1), jnp.int32).at[idx].add(w)


# -- device-side sketch observation (HLL registers, CMS rows) ----------------
# Parity: upstream's StatsScan evaluates the Stat sketches INSIDE the
# tablet-server iterator (SURVEY.md:266-274); round 2 still hashed on the
# host (~3.9s for a 67M HLL observation). These kernels run the identical
# FNV/fmix64 hash + fold pipeline on device and emit the tiny mergeable
# state (4 KB of registers / a [depth, width] table) for the host sketch
# objects to fold in — bit-identical to stats.sketches._hash64's numeric
# fast path, so device- and host-observed sketches merge losslessly.

# The hash family is PURE 32-bit (2x murmur32 fmix over the value's
# 32-bit halves, floats canonicalized via their f32 bit pattern) because
# the TPU x64 rewriter has no lowering for 64-bit bitcasts — mirrored
# bit-for-bit by stats.sketches._hash64_numeric (HASH_VERSION v2).

_M32_1 = 0x85EBCA6B
_M32_2 = 0xC2B2AE35


def _fmix32_dev(h: jax.Array) -> jax.Array:
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(_M32_1)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(_M32_2)
    h = h ^ (h >> jnp.uint32(16))
    return h


def _halves_u32_dev(v: jax.Array):
    """(lo, hi) u32 halves — mirrors stats.sketches._halves_u32."""
    if v.dtype.kind == "f":
        lo = jax.lax.bitcast_convert_type(
            v.astype(jnp.float32), jnp.uint32
        )
        return lo, jnp.zeros_like(lo)
    iv = v.astype(jnp.int64)
    lo = (iv & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = ((iv >> jnp.int64(32)) & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    return lo, hi


def _hash_pair_dev(v: jax.Array, seed: int):
    s1 = jnp.uint32((seed * 0x9E3779B9 + 0x165667B1) & 0xFFFFFFFF)
    s2 = jnp.uint32((seed * 0x85EBCA77 + 0x27D4EB2F) & 0xFFFFFFFF)
    lo, hi = _halves_u32_dev(v)
    h1 = _fmix32_dev(lo ^ _fmix32_dev(hi ^ s1))
    h2 = _fmix32_dev(h1 ^ hi ^ s2)
    return h1, h2


def _bit_length_u32_dev(x: jax.Array) -> jax.Array:
    """bit_length of u32 (0 -> 0) via the f32 exponent field — matches
    the host's float-conversion rounding (round-to-nearest on both
    sides), so ranks agree bit-for-bit."""
    f = x.astype(jnp.float32)
    exp = (
        (jax.lax.bitcast_convert_type(f, jnp.uint32) >> jnp.uint32(23))
        .astype(jnp.int32) & 0xFF
    )
    return jnp.where(x > 0, exp - 126, 0)


@functools.partial(jax.jit, static_argnames=("p",))
def hll_registers(v: jax.Array, mask: jax.Array, p: int = 12) -> jax.Array:
    """Masked HyperLogLog register fold on device -> [2^p] int32 ranks.

    Same index/rank rules as stats.sketches.Cardinality._observe_chunk
    over the v2 numeric hash: idx = top p bits of h1; rank = 1-based
    first-1-bit of the remaining 64-p bits of (h1, h2). Fold with
    Cardinality.observe_registers — registers agree bit-for-bit with a
    host observation of the same values, so max-merge is lossless."""
    m = 1 << p
    h1, h2 = _hash_pair_dev(v, 0)
    idx = (h1 >> jnp.uint32(32 - p)).astype(jnp.int32)
    # rest (as the host sees it): the u64 (h1<<32|h2) shifted left by p
    rest_hi = (h1 << jnp.uint32(p)) | (h2 >> jnp.uint32(32 - p))
    rest_lo = h2 << jnp.uint32(p)
    bl_hi = _bit_length_u32_dev(rest_hi)
    bl_lo = _bit_length_u32_dev(rest_lo)
    rank = jnp.where(
        rest_hi > 0,
        65 - (bl_hi + 32),
        jnp.where(rest_lo > 0, 65 - bl_lo, 64 - p + 1),
    )
    rank = jnp.where(mask, rank, 0).astype(jnp.int32)
    return jnp.zeros(m, jnp.int32).at[idx].max(rank, mode="drop")


@functools.partial(jax.jit, static_argnames=("width", "depth"))
def cms_table(
    v: jax.Array, mask: jax.Array, width: int = 1024, depth: int = 4
) -> jax.Array:
    """Masked Count-Min observation on device -> [depth, width] int32.

    NUMERIC-KEYED: rows hash the value's canonical pattern (seed d+1),
    the same v2 family as Frequency._cols on numeric input — fold with
    Frequency.observe_table (numeric_keys sketches only; the flag is
    enforced there and in merge/from_json). The column index matches the
    host's (h1*2^32 + h2) % width via modular arithmetic in i64."""
    w = jnp.where(mask, 1, 0).astype(jnp.int32)
    rows = []
    for d in range(depth):
        h1, h2 = _hash_pair_dev(v, d + 1)
        two32_mod = (1 << 32) % width
        col = (
            (h1.astype(jnp.int64) % width) * two32_mod
            + h2.astype(jnp.int64)
        ) % width
        rows.append(
            jnp.zeros(width, jnp.int32).at[col.astype(jnp.int32)].add(w)
        )
    return jnp.stack(rows)


# -- grouped (segment) reductions: the device side of SQL GROUP BY ----------
# Parity: upstream runs GROUP BY aggregation in Spark after the relation
# scan (SURVEY.md:381-383 GeoMesaRelation); here the grouped reduction IS a
# device kernel — one masked segment reduction per aggregate, mergeable
# across shards by the same add/min/max laws the sketches use.


@functools.partial(jax.jit, static_argnames=("num_groups",))
def grouped_count(gids: jax.Array, mask: jax.Array, num_groups: int) -> jax.Array:
    return jax.ops.segment_sum(
        mask.astype(jnp.int64), gids, num_segments=num_groups
    )


@functools.partial(jax.jit, static_argnames=("num_groups",))
def grouped_sum(
    v: jax.Array, gids: jax.Array, mask: jax.Array, num_groups: int
) -> jax.Array:
    vf = jnp.where(mask, v.astype(jnp.float64), 0.0)  # gt: f64-refine
    return jax.ops.segment_sum(vf, gids, num_segments=num_groups)


@functools.partial(jax.jit, static_argnames=("num_groups",))
def grouped_min(
    v: jax.Array, gids: jax.Array, mask: jax.Array, num_groups: int
) -> jax.Array:
    vf = jnp.where(mask, v.astype(jnp.float64), jnp.inf)  # gt: f64-refine
    return jax.ops.segment_min(vf, gids, num_segments=num_groups)


@functools.partial(jax.jit, static_argnames=("num_groups",))
def grouped_max(
    v: jax.Array, gids: jax.Array, mask: jax.Array, num_groups: int
) -> jax.Array:
    vf = jnp.where(mask, v.astype(jnp.float64), -jnp.inf)  # gt: f64-refine
    return jax.ops.segment_max(vf, gids, num_segments=num_groups)


@functools.partial(jax.jit, static_argnames=("n_time_bins", "bins_per_dim"))
def z3_histogram(
    x: jax.Array,
    y: jax.Array,
    t_bin: jax.Array,
    mask: jax.Array,
    n_time_bins: int,
    bins_per_dim: int = 16,
) -> jax.Array:
    """Coarse (time-bin, x-cell, y-cell) occupancy counts (Z3Histogram
    parity): the planner's selectivity estimator for spatio-temporal cost."""
    cx = jnp.clip(
        jnp.floor((x + 180.0) / 360.0 * bins_per_dim).astype(jnp.int32),
        0,
        bins_per_dim - 1,
    )
    cy = jnp.clip(
        jnp.floor((y + 90.0) / 180.0 * bins_per_dim).astype(jnp.int32),
        0,
        bins_per_dim - 1,
    )
    tb = jnp.clip(t_bin, 0, n_time_bins - 1)
    flat = (tb * bins_per_dim + cy) * bins_per_dim + cx
    w = mask.astype(jnp.int32)
    out = jnp.zeros(n_time_bins * bins_per_dim * bins_per_dim, jnp.int32)
    return out.at[flat].add(w).reshape(n_time_bins, bins_per_dim, bins_per_dim)


def stats_sharded(mesh: Mesh, fn, *arrays):
    """Run a masked reduction per shard and psum-merge the results.

    `fn(*local_arrays)` must return a pytree of summable partials (counts,
    sums, histograms). For min/max use the component trick (negate) or
    dedicated lax collectives in a custom fn.
    """

    @functools.partial(
        _shard_map,
        mesh=mesh,
        in_specs=tuple(P(SHARD_AXIS) for _ in arrays),
        out_specs=P(),
    )
    def run(*local):
        return jax.tree.map(lambda t: jax.lax.psum(t, SHARD_AXIS), fn(*local))

    return run(*arrays)
