"""Typed system-property/flag registry.

Parity: GeoMesaSystemProperties (geomesa-utils o.l.g.utils.conf) [upstream,
unverified]: typed properties with env-var fallback, defaults, and
provenance. Property "geomesa.scan.ranges.target" maps to env
GEOMESA_TPU_SCAN_RANGES_TARGET (flag names keep the upstream dotted names).
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Callable, Dict, Optional


@dataclasses.dataclass
class SystemProperty:
    name: str  # dotted, e.g. "geomesa.scan.ranges.target"
    default: object
    parser: Callable[[str], object]
    description: str = ""

    @property
    def env_name(self) -> str:
        return self.name.upper().replace(".", "_").replace("GEOMESA_", "GEOMESA_TPU_", 1)

    def get(self) -> object:
        override = _overrides.get(self.name)
        if override is not None:
            return override
        raw = os.environ.get(self.env_name)
        if raw is not None:
            return self.parser(raw)
        return self.default

    @property
    def provenance(self) -> str:
        if self.name in _overrides:
            return "override"
        if self.env_name in os.environ:
            return f"env:{self.env_name}"
        return "default"


_overrides: Dict[str, object] = {}
_lock = threading.Lock()


class SystemProperties:
    """The flag registry (upstream: GeoMesaSystemProperties object)."""

    SCAN_RANGES_TARGET = SystemProperty(
        "geomesa.scan.ranges.target", 2000, int,
        "z-range decomposition budget (more ranges = tighter covering)",
    )
    QUERY_TIMEOUT_MS = SystemProperty(
        "geomesa.query.timeout", 0, int, "per-query timeout in ms; 0 = none"
    )
    FORCE_COUNT = SystemProperty(
        "geomesa.force.count", False, lambda s: s.lower() in ("1", "true"),
        "exact counts by default (vs manifest estimates)",
    )
    SCAN_BATCH_SIZE = SystemProperty(
        "geomesa.scan.batch.size", 1 << 20, int,
        "target features per device batch on the scan path",
    )
    COORD_DTYPE = SystemProperty(
        "geomesa.coord.dtype", "float32", str,
        "device coordinate dtype (float32|float64)",
    )
    SCAN_BLOCK_FULL_TABLE = SystemProperty(
        "geomesa.scan.block.full.table", False,
        lambda s: s.lower() in ("1", "true"),
        "reject queries whose filter constrains nothing (full-table scans)",
    )
    SQL_JOIN_MAX_ROWS = SystemProperty(
        "geomesa.sql.join.max.rows", 1 << 25, int,
        "per-side row cap for SQL joins (the join itself is a host-side "
        "hash/kernel join over materialized sides; a silent 67M-row "
        "materialization would exhaust host memory — push filters into "
        "the WHERE clause or raise the cap deliberately)",
    )
    PROFILE_DIR = SystemProperty(
        "geomesa.profile.dir", "", str,
        "emit a jax profiler trace per query execution into this directory",
    )
    SPATIAL_PREP_CACHE_DIR = SystemProperty(
        "geomesa.spatial.prep.cache.dir", "", str,
        "disk cache directory for polygon-layer prep structures (pair "
        "lists / padded edge tables — the prepared-geometry analog); "
        "empty = in-process cache only",
    )
    KNN_FULLSCAN_SELECTIVITY = SystemProperty(
        "geomesa.knn.fullscan.selectivity", 0.5, float,
        "kNN auto kernel choice: estimated filter selectivity at or above "
        "which the dense fullscan replaces the sparse tile scan (stats-"
        "driven StrategyDecider analog; sparse pruning cannot win when "
        "nearly every data tile bears a match)",
    )
    COMPILE_CACHE_DIR = SystemProperty(
        "geomesa.compile.cache.dir", "", str,
        "persistent XLA compilation-cache directory shared by the "
        "planner, QueryService, gmtpu serve and bench (empty = "
        "$JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache "
        "with a per-backend subdir; 'off' disables)",
    )
    LOAD_INTERCEPTORS = SystemProperty(
        "geomesa.query.interceptors.load", False,
        lambda s: s.lower() in ("1", "true"),
        "allow dotted-path interceptor classes from SFT user_data to be "
        "imported and instantiated (schema metadata round-trips through "
        "converter configs and store manifests, so arbitrary-import is "
        "opt-in; the built-in 'full-table-scan-guard' always loads)",
    )

    _all = None

    @classmethod
    def all(cls) -> Dict[str, SystemProperty]:
        if cls._all is None:
            cls._all = {
                v.name: v
                for v in vars(cls).values()
                if isinstance(v, SystemProperty)
            }
        return cls._all

    @staticmethod
    def set(name: str, value: object) -> None:
        with _lock:
            _overrides[name] = value

    @staticmethod
    def clear(name: Optional[str] = None) -> None:
        with _lock:
            if name is None:
                _overrides.clear()
            else:
                _overrides.pop(name, None)
