"""Predicate compiler: filter AST -> jitted mask function over device columns.

Parity role: geomesa-filter's FastFilterFactory (optimized filter evaluation
with pre-resolved accessors and prepared geometries) plus the server-side
residual-filter check inside the reference's iterators [upstream,
unverified]. TPU-first design:

- the *structure* of the filter is baked into a pure function (XLA fuses the
  whole predicate tree into one elementwise kernel over the batch);
- per-batch *values* (dictionary-code tables, polygon edge tables, bounds)
  are passed as a params pytree, so a recompiled vocabulary never retraces
  as long as shapes hold;
- string predicates (=, <>, <, LIKE, IN) all lower to one mechanism: a
  host-computed boolean "allowed" table over the batch vocabulary, gathered
  by dictionary code on device — the columnar analog of the reference's
  lazy-attribute trick (only touch what the filter needs);
- geometry predicates on point data lower to bbox compares / crossing-number
  point-in-polygon / haversine distance; extended-geometry data delegates to
  engine.geometry CSR kernels.

Null semantics: dictionary code -1 = null; any comparison on null is False
(matching SQL/CQL three-valued logic collapsing to False at the top level).
Float NaN is treated as null for IS NULL on numeric columns.
"""

from __future__ import annotations

import re
import time
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from geomesa_tpu.core.columnar import DictColumn, FeatureBatch, GeometryColumn
from geomesa_tpu.core.sft import SimpleFeatureType
from geomesa_tpu.core.wkt import Geometry
from geomesa_tpu.cql import ast
from geomesa_tpu.engine.device import VALID, DeviceBatch
from geomesa_tpu.engine.geodesy import haversine_m, point_to_segments_m
from geomesa_tpu.engine.pip import points_in_polygon, polygon_edges

ParamBuilder = Callable[[FeatureBatch], np.ndarray]


def f32_ulp_band(bound: float) -> np.float32:
    """Half-width of the f32 ambiguity band around a comparison bound:
    values whose f32 rounding can land on the other side of `bound`.
    4x the half-ulp covers the rounding of both the coordinate and the
    compare operand. Shared by the compiled-filter band and the bench's
    exact-count gate (one definition — they must not drift)."""
    return np.float32(max(abs(bound), 1.0) * 2.0 ** -24 * 4)


class CompiledFilter:
    """A compiled filter: `mask(dev, batch)` -> bool [N] device array.

    When the filter contains polygon predicates, `band(dev, batch)` flags
    rows inside the f32 boundary-ambiguity band and `mask_refined`
    re-evaluates exactly those rows in f64 on host (cql.hosteval) and
    patches the mask — the SURVEY.md:824-827 robustness plan: device bulk
    throughput, oracle-exact answers at the boundary."""

    def __init__(
        self, fn, builders: Dict[str, ParamBuilder], cql: str,
        filter_ast=None, band_fn=None,
    ):
        self._fn = fn
        self._jit = jax.jit(fn)
        self.builders = builders
        self.cql = cql
        self.filter_ast = filter_ast
        self._band_fn = band_fn
        self._band_jit = jax.jit(band_fn) if band_fn is not None else None

    def params(self, batch: FeatureBatch) -> Dict[str, np.ndarray]:
        return {k: b(batch) for k, b in self.builders.items()}

    def _metered(self, jit_fn, which: str, *args) -> jax.Array:
        """Dispatch through `jit_fn`, metering the inline compile stall:
        compile_filter() only builds closures — the ~0.65s XLA compile
        happens HERE, at the first call per shape bucket, and that call
        blocks through trace+compile. Non-compiling calls discard the
        timestamps (async dispatch returns immediately, so the wall
        would measure dispatch, not execution — deliberately unsynced,
        we only keep it when the cache grew)."""
        before = (jit_fn._cache_size()
                  if hasattr(jit_fn, "_cache_size") else -1)
        t0 = time.perf_counter()
        out = jit_fn(*args)
        if before >= 0 and jit_fn._cache_size() > before:
            dt = time.perf_counter() - t0
            try:
                from geomesa_tpu.compilecache.stall import STALLS
                from geomesa_tpu.utils.metrics import metrics

                metrics.histogram("plan.filter.compile").update(dt)
                STALLS.note(f"filter:{which}:{self.cql[:64]}", dt)
            except Exception:
                pass  # observability must never fail the query
        return out

    def mask(self, dev: DeviceBatch, batch: FeatureBatch) -> jax.Array:
        return self._metered(self._jit, "mask", self.params(batch), dev)

    @property
    def has_band(self) -> bool:
        return self._band_jit is not None

    def band(self, dev: DeviceBatch, batch: FeatureBatch) -> jax.Array:
        """Boundary-ambiguity flags [N] (False everywhere when the filter
        has no polygon predicate)."""
        if self._band_jit is None:
            raise ValueError("filter has no boundary band")
        return self._metered(self._band_jit, "band",
                             self.params(batch), dev)

    def refine(
        self, mask: np.ndarray, dev: DeviceBatch, batch: FeatureBatch
    ) -> np.ndarray:
        """Patch an already-fetched host mask: borderline rows (f32
        boundary band of any polygon predicate) are re-evaluated in f64.
        No-op when the filter has no polygon predicate."""
        if self._band_jit is None or self.filter_ast is None:
            return mask
        flags = np.asarray(self.band(dev, batch))
        idx = np.nonzero(flags)[0]
        if not len(idx):
            return mask
        from geomesa_tpu.cql.hosteval import eval_filter_host

        sub = batch.select(idx)
        mask = mask.copy()
        mask[idx] = eval_filter_host(self.filter_ast, sub)
        return mask

    def mask_refined(self, dev: DeviceBatch, batch: FeatureBatch) -> np.ndarray:
        """Host mask with borderline rows re-evaluated exactly in f64."""
        return self.refine(np.asarray(self.mask(dev, batch)), dev, batch)

    def count_exact(
        self, dev: DeviceBatch, batch: FeatureBatch, extra=None
    ) -> int:
        """Bit-exact match count WITHOUT fetching the full mask: the
        device count is corrected by re-evaluating only the (few) band
        rows in f64 on host. `extra` ANDs an additional device mask
        (partition pruning / visibility) into both the count and the
        band, so corrections respect it. One scalar + one small index
        fetch; the f64-oracle-exact answer at device cost."""
        m = self.mask(dev, batch)
        if extra is not None:
            m = m & extra
        total = int(np.asarray(jnp.sum(m, dtype=jnp.int64)))
        return total + self.band_count_correction(dev, batch, m, extra)

    def band_count_correction(
        self, dev: DeviceBatch, batch: FeatureBatch, m=None, extra=None
    ) -> int:
        """(exact - approximate) match count over the band rows: add this
        to a device mask count to make it f64-exact. 0 when band-free.

        The steady-state (no band rows matched) cost is ONE fused
        dispatch + one scalar fetch: the original eager op chain (band,
        AND, sum, nonzero, gather, sum) cost ~5 dispatches per query,
        each with its own dispatch overhead. `m` is accepted for signature compatibility
        but recomputed inside the fused jit (jit-cached, free)."""
        if self._band_jit is None or self.filter_ast is None:
            return 0
        self._ensure_band_jits()
        params = self.params(batch)
        idx, approx = self._band_rows(params, dev, extra, len(batch))
        if not len(idx):
            return 0
        from geomesa_tpu.cql.hosteval import eval_filter_host

        exact = int(eval_filter_host(self.filter_ast,
                                     batch.select(idx)).sum())
        return exact - approx

    def _band_rows(self, params, dev, extra, nrows: int):
        """ONE fused dispatch: (band-row indices, approximate in-mask
        count over them). The compaction capacity starts at 64 and
        grows 4x on saturation (pow2 keeps the jit cache stable), so
        the no-band and few-band steady states — the common case every
        query pays — cost a single dispatch + a KB fetch instead of a
        separate count round trip."""
        k = 64
        while True:
            idx, approx = jax.device_get(
                self._cx_gather(params, dev, extra, k=k))
            idx = idx[idx < nrows].astype(np.int64)
            if len(idx) < k or k >= nrows:
                return idx, int(approx)
            k *= 4

    def _ensure_band_jits(self):
        """The fused fixed-size-compaction jit over the band, shared by
        band_count_correction and band_corrections (both go through
        _band_rows' grow loop; the separate count jit it once paired
        with was dead after that rewrite — lint rule GT05's seed)."""
        if hasattr(self, "_cx_gather"):
            return
        band_fn = self._band_fn
        mask_fn = self._fn

        def _gather(params, dev, extra, k):
            b = band_fn(params, dev)
            mm = mask_fn(params, dev)
            if extra is not None:
                b = b & extra
                mm = mm & extra
            n = b.shape[0]
            TL = 512
            if n < TL or n % TL:
                # small/odd batches: direct compaction is already cheap
                idx = jnp.nonzero(b, size=k, fill_value=n)[0]
            else:
                # two-stage compaction: flat jnp.nonzero over the full
                # vector measured 5.6 s at 67M on TPU (the round-5
                # product-path regression); tile-flags first (cheap
                # reduction), then nonzero over only the <=k flagged
                # tiles' rows (each band row needs at most its own
                # tile, so k tiles always suffice). 112 ms at 67M.
                nt = n // TL
                bt = b.reshape(nt, TL)
                t_cnt = min(k, nt)
                tsel = jnp.nonzero(
                    jnp.any(bt, axis=1), size=t_cnt, fill_value=nt)[0]
                blk = jnp.where(
                    (tsel < nt)[:, None],
                    bt[jnp.minimum(tsel, nt - 1)], False)
                loc = jnp.nonzero(
                    blk.reshape(-1), size=k, fill_value=t_cnt * TL)[0]
                t_of = jnp.minimum(loc // TL, t_cnt - 1)
                idx = jnp.where(
                    loc < t_cnt * TL, tsel[t_of] * TL + loc % TL, n)
            live = idx < n
            approx = jnp.sum(
                mm[jnp.minimum(idx, n - 1)] & live, dtype=jnp.int32)
            return idx, approx

        self._cx_gather = jax.jit(_gather, static_argnames=("k",))

    def band_corrections(self, dev: DeviceBatch, batch: FeatureBatch):
        """Exact f64 membership for the rows inside the f32 boundary
        band, as (idx int64 [m], exact bool [m]) — the DEVICE-RESIDENT
        refinement primitive. Callers scatter `exact` (ANDed with any
        per-row extra components — validity, partition allowance) into
        their device mask at `idx`:

            mask = mask.at[jnp.asarray(idx)].set(jnp.asarray(vals))

        instead of round-tripping the full mask through the host: the
        fetch-patch-reupload `refine` path moved the whole mask to the
        host and back on every query; this costs one fused dispatch + a
        KB-sized index
        fetch. Indices come from a fixed-size device compaction (the
        band_count_correction idiom), sized to the band count's pow2."""
        empty = (np.zeros(0, np.int64), np.zeros(0, bool))
        if self._band_jit is None or self.filter_ast is None:
            return empty
        self._ensure_band_jits()
        params = self.params(batch)
        idx, _ = self._band_rows(params, dev, None, len(batch))
        if not len(idx):
            return empty
        from geomesa_tpu.cql.hosteval import eval_filter_host

        exact = np.asarray(
            eval_filter_host(self.filter_ast, batch.select(idx)), bool)
        return idx, exact

    def mask_fn(self):
        """The raw pure function (params, dev) -> mask, for fusion into
        larger kernels (aggregations AND it in rather than materializing)."""
        return self._fn

    def __repr__(self):
        return f"CompiledFilter({self.cql!r})"


def compile_filter(f: ast.Filter, sft: SimpleFeatureType) -> CompiledFilter:
    builders: Dict[str, ParamBuilder] = {}
    counter = [0]
    bands: List = []
    fn = _compile(f, sft, builders, counter, bands)

    def top(params, dev):
        return fn(params, dev) & dev[VALID]

    band_fn = None
    if bands:
        def band_fn(params, dev, _bands=tuple(bands)):
            m = _bands[0](params, dev)
            for g in _bands[1:]:
                m = m | g(params, dev)
            return m & dev[VALID]

    return CompiledFilter(top, builders, ast.to_cql(f), f, band_fn)


# -- helpers ---------------------------------------------------------------


def _key(counter: List[int]) -> str:
    counter[0] += 1
    return f"p{counter[0]}"


def _attr(sft: SimpleFeatureType, name: str):
    if name not in sft:
        raise ValueError(f"unknown attribute {name!r} in filter (sft {sft.name!r})")
    return sft.attribute(name)


def _like_to_regex(pattern: str, case_insensitive: bool) -> "re.Pattern":
    # CQL LIKE: % = any run, _ = single char, \ escapes
    out = []
    i = 0
    while i < len(pattern):
        c = pattern[i]
        if c == "\\" and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 2
            continue
        if c == "%":
            out.append(".*")
        elif c == "_":
            out.append(".")
        else:
            out.append(re.escape(c))
        i += 1
    return re.compile("^" + "".join(out) + "$", re.IGNORECASE if case_insensitive else 0)


def _allowed_table(
    name: str, pred: Callable[[str], bool]
) -> ParamBuilder:
    """Builder producing a bool table over the batch's vocab for `name`."""

    def build(batch: FeatureBatch) -> np.ndarray:
        col = batch.columns[name]
        assert isinstance(col, DictColumn)
        if not col.vocab:
            return np.zeros(1, dtype=bool)
        return np.array([pred(v) for v in col.vocab], dtype=bool)

    return build


def _gather_allowed(table, codes):
    safe = jnp.clip(codes, 0, table.shape[0] - 1)
    return jnp.where(codes >= 0, table[safe], False)


_NUM_OPS = {
    "=": lambda a, b: a == b,
    "<>": lambda a, b: a != b,
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
}

_STR_OPS = {
    "=": lambda v, lit: v == lit,
    "<>": lambda v, lit: v != lit,
    "<": lambda v, lit: v < lit,
    "<=": lambda v, lit: v <= lit,
    ">": lambda v, lit: v > lit,
    ">=": lambda v, lit: v >= lit,
}


# -- node compilation ------------------------------------------------------


def _compile(f: ast.Filter, sft, builders, counter, bands=None):
    if isinstance(f, ast.Include):
        return lambda params, dev: jnp.ones_like(dev[VALID])
    if isinstance(f, ast.Exclude):
        return lambda params, dev: jnp.zeros_like(dev[VALID])
    if isinstance(f, ast.And):
        fns = [_compile(c, sft, builders, counter, bands) for c in f.children]
        def and_(params, dev):
            m = fns[0](params, dev)
            for g in fns[1:]:
                m = m & g(params, dev)
            return m
        return and_
    if isinstance(f, ast.Or):
        fns = [_compile(c, sft, builders, counter, bands) for c in f.children]
        def or_(params, dev):
            m = fns[0](params, dev)
            for g in fns[1:]:
                m = m | g(params, dev)
            return m
        return or_
    if isinstance(f, ast.Not):
        g = _compile(f.child, sft, builders, counter, bands)
        return lambda params, dev: ~g(params, dev)
    if isinstance(f, ast.Comparison):
        return _compile_comparison(f, sft, builders, counter)
    if isinstance(f, ast.Between):
        a = _attr(sft, f.prop.name)
        neg = f.negate
        if a.type in ("String", "UUID"):
            lo, hi = str(f.lo.value), str(f.hi.value)
            k = _key(counter)
            pred = (lambda v: not lo <= v <= hi) if neg else (lambda v: lo <= v <= hi)
            builders[k] = _allowed_table(a.name, pred)
            return lambda params, dev, k=k, n=a.name: _gather_allowed(params[k], dev[n])
        lo = _literal_value(f.lo, a)
        hi = _literal_value(f.hi, a)
        def between(params, dev, n=a.name):
            m = (dev[n] >= lo) & (dev[n] <= hi)
            return ~m if neg else m
        return between
    if isinstance(f, ast.Like):
        a = _attr(sft, f.prop.name)
        if a.type not in ("String", "UUID"):
            raise ValueError(f"LIKE on non-string attribute {a.name!r}")
        rx = _like_to_regex(f.pattern, f.case_insensitive)
        k = _key(counter)
        builders[k] = _allowed_table(a.name, lambda v: rx.match(v) is not None)
        neg = f.negate
        def like(params, dev, k=k, n=a.name):
            m = _gather_allowed(params[k], dev[n])
            return ~m & (dev[n] >= 0) if neg else m
        return like
    if isinstance(f, ast.In):
        a = _attr(sft, f.prop.name)
        if a.type in ("String", "UUID"):
            vals = {str(v) for v in f.values}
            k = _key(counter)
            builders[k] = _allowed_table(a.name, lambda v: v in vals)
            neg = f.negate
            def isin(params, dev, k=k, n=a.name):
                m = _gather_allowed(params[k], dev[n])
                return ~m & (dev[n] >= 0) if neg else m
            return isin
        vals = np.array(sorted(float(v) for v in f.values))
        def isin_num(params, dev, n=a.name, vals=vals):
            m = jnp.isin(dev[n], jnp.asarray(vals, dev[n].dtype))
            return ~m if f.negate else m
        return isin_num
    if isinstance(f, ast.IsNull):
        a = _attr(sft, f.prop.name)
        neg = f.negate
        if a.type in ("String", "UUID"):
            def isnull(params, dev, n=a.name):
                m = dev[n] < 0
                return ~m if neg else m
            return isnull
        if a.type in ("Double", "Float"):
            def isnan(params, dev, n=a.name):
                m = jnp.isnan(dev[n])
                return ~m if neg else m
            return isnan
        # int/temporal columns have no null representation on device
        return lambda params, dev: (
            jnp.ones_like(dev[VALID]) if neg else jnp.zeros_like(dev[VALID])
        )
    if isinstance(f, ast.TemporalPredicate):
        a = _attr(sft, f.prop.name)
        if not a.is_temporal:
            raise ValueError(f"temporal predicate on non-date attribute {a.name!r}")
        n = a.name
        if f.op == "DURING":
            s, e = jnp.int64(f.start), jnp.int64(f.end)
            return lambda params, dev: (dev[n] > s) & (dev[n] < e)
        v = jnp.int64(f.start)
        if f.op == "BEFORE":
            return lambda params, dev: dev[n] < v
        if f.op == "AFTER":
            return lambda params, dev: dev[n] > v
        return lambda params, dev: dev[n] == v  # TEQUALS
    if isinstance(f, ast.SpatialPredicate):
        return _compile_spatial(f, sft, builders, counter, bands)
    if isinstance(f, ast.DistancePredicate):
        return _compile_distance(f, sft, builders, counter)
    raise NotImplementedError(f"cannot compile {type(f).__name__}")


def _literal_value(lit: ast.Literal, attr):
    if attr.is_temporal:
        if lit.kind != "datetime":
            raise ValueError(f"non-datetime literal for {attr.name!r}")
        return jnp.int64(int(lit.value))
    return lit.value


def _compile_comparison(f: ast.Comparison, sft, builders, counter):
    # normalize: Property op Expr
    left, right, op = f.left, f.right, f.op
    if isinstance(left, ast.Literal) and isinstance(right, ast.Property):
        flip = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "=": "=", "<>": "<>"}
        left, right, op = right, left, flip[op]
    if not isinstance(left, ast.Property):
        raise ValueError("comparison requires at least one property operand")
    a = _attr(sft, left.name)

    if isinstance(right, ast.Property):
        b = _attr(sft, right.name)
        if a.type in ("String", "UUID") or b.type in ("String", "UUID"):
            raise NotImplementedError("string property-to-property comparison")
        fn = _NUM_OPS[op]
        return lambda params, dev: fn(dev[a.name], dev[b.name])

    if a.type in ("String", "UUID"):
        lit = str(right.value)
        pred = _STR_OPS[op]
        k = _key(counter)
        builders[k] = _allowed_table(a.name, lambda v: pred(v, lit))
        return lambda params, dev, k=k, n=a.name: _gather_allowed(params[k], dev[n])

    v = _literal_value(right, a)
    if isinstance(v, bool):
        v = jnp.bool_(v)
    fn = _NUM_OPS[op]
    return lambda params, dev: fn(dev[a.name], v)


# -- spatial ---------------------------------------------------------------


def _compile_spatial(f: ast.SpatialPredicate, sft, builders, counter, bands=None):
    a = _attr(sft, f.prop.name)
    if not a.is_geometry:
        raise ValueError(f"spatial predicate on non-geometry {a.name!r}")
    if a.type != "Point":
        from geomesa_tpu.engine import geometry as eg

        return eg.compile_extended_spatial(f, a.name, a.type)
    n = a.name
    g = f.geometry
    op = f.op

    if op == "BBOX":
        x0, y0, x1, y1 = g.bbox
        def bbox(params, dev):
            return (
                (dev[f"{n}__x"] >= x0)
                & (dev[f"{n}__x"] <= x1)
                & (dev[f"{n}__y"] >= y0)
                & (dev[f"{n}__y"] <= y1)
            )
        if bands is not None:
            # f32 boundary band (round 4, VERDICT #5): coordinates within
            # the ulp band of a bbox edge can flip sides when the device
            # column is f32 — flag them for f64 host refinement so counts
            # are bit-exact vs the f64 oracle.
            ex0, ex1 = f32_ulp_band(x0), f32_ulp_band(x1)
            ey0, ey1 = f32_ulp_band(y0), f32_ulp_band(y1)

            def bbox_band(params, dev):
                X = dev[f"{n}__x"]
                Y = dev[f"{n}__y"]
                return (
                    (jnp.abs(X - x0) <= ex0) | (jnp.abs(X - x1) <= ex1)
                    | (jnp.abs(Y - y0) <= ey0) | (jnp.abs(Y - y1) <= ey1)
                )

            bands.append(bbox_band)
        return bbox

    if op in ("INTERSECTS", "WITHIN", "DISJOINT"):
        base = _point_intersects(n, g, bands)
        if op == "DISJOINT":
            return lambda params, dev: ~base(params, dev)
        return base

    if op in ("EQUALS", "CONTAINS"):
        # a point can only equal/contain a coincident point literal
        if g.kind in ("Point", "MultiPoint"):
            pts = np.concatenate(g.rings, axis=0)
            def eq(params, dev):
                m = jnp.zeros_like(dev[VALID])
                for px, py in pts:
                    m = m | ((dev[f"{n}__x"] == px) & (dev[f"{n}__y"] == py))
                return m
            return eq
        return lambda params, dev: jnp.zeros_like(dev[VALID])

    if op == "TOUCHES":
        # point touches an area/line iff it lies on the boundary; a point
        # literal has no boundary, so nothing can touch it (DE-9IM)
        x1e, y1e, x2e, y2e = polygon_edges(g)
        if len(x1e) == 0:
            return lambda params, dev: jnp.zeros_like(dev[VALID])
        segs = tuple(jnp.asarray(s) for s in (x1e, y1e, x2e, y2e))
        def touches(params, dev):
            d = point_to_segments_m(dev[f"{n}__x"], dev[f"{n}__y"], *segs)
            return d <= 0.5  # within half a meter of the boundary (f32 floor)
        return touches

    if op in ("OVERLAPS", "CROSSES"):
        # DE-9IM: a point can never overlap or cross anything
        return lambda params, dev: jnp.zeros_like(dev[VALID])

    raise NotImplementedError(f"spatial op {op}")


def _point_intersects(n: str, g: Geometry, bands=None):
    """intersects/within for point data against a geometry literal."""
    if g.kind in ("Point", "MultiPoint"):
        pts = np.concatenate(g.rings, axis=0) if g.rings else np.zeros((0, 2))
        def eq(params, dev):
            m = jnp.zeros_like(dev[VALID])
            for px, py in pts:
                m = m | ((dev[f"{n}__x"] == px) & (dev[f"{n}__y"] == py))
            return m
        return eq
    if g.kind in ("LineString", "MultiLineString"):
        x1e, y1e, x2e, y2e = polygon_edges(g)
        segs = tuple(jnp.asarray(s) for s in (x1e, y1e, x2e, y2e))
        def online(params, dev):
            d = point_to_segments_m(dev[f"{n}__x"], dev[f"{n}__y"], *segs)
            return d <= 0.5
        return online
    # polygon-like: even-odd point-in-polygon over the edge table
    x1e, y1e, x2e, y2e = polygon_edges(g)
    edges = tuple(jnp.asarray(s) for s in (x1e, y1e, x2e, y2e))
    def pip(params, dev):
        return points_in_polygon(dev[f"{n}__x"], dev[f"{n}__y"], *edges)
    if bands is not None:
        # f32 boundary ambiguity band for exact refinement: rows flagged
        # here get re-evaluated in f64 on host (SURVEY.md:824-827 plan;
        # see CompiledFilter.mask_refined)
        from geomesa_tpu.engine.pip import points_in_polygon_band

        def band(params, dev):
            return points_in_polygon_band(
                dev[f"{n}__x"], dev[f"{n}__y"], *edges
            )

        bands.append(band)
    return pip


def _compile_distance(f: ast.DistancePredicate, sft, builders, counter):
    a = _attr(sft, f.prop.name)
    if a.type != "Point":
        from geomesa_tpu.engine import geometry as eg

        return eg.compile_extended_spatial(f, a.name, a.type)
    n = a.name
    g = f.geometry
    d = float(f.distance_m)

    if g.kind in ("Point", "MultiPoint") and sum(len(r) for r in g.rings) == 1:
        px, py = g.point
        def near(params, dev):
            return haversine_m(dev[f"{n}__x"], dev[f"{n}__y"], px, py) <= d
        base = near
    else:
        x1e, y1e, x2e, y2e = polygon_edges(g)
        if len(x1e) == 0:  # point-cloud literal: degenerate segments
            pts = np.concatenate(g.rings, axis=0)
            x1e = x2e = pts[:, 0]
            y1e = y2e = pts[:, 1]
        segs = tuple(jnp.asarray(s) for s in (x1e, y1e, x2e, y2e))
        inside = (
            _point_intersects(n, g)
            if g.kind in ("Polygon", "MultiPolygon")
            else None
        )
        def near_seg(params, dev):
            m = point_to_segments_m(dev[f"{n}__x"], dev[f"{n}__y"], *segs) <= d
            if inside is not None:
                m = m | inside(params, dev)
            return m
        base = near_seg

    if f.op == "BEYOND":
        return lambda params, dev: ~base(params, dev)
    return base
