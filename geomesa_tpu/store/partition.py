"""Partition schemes: feature -> partition path; query bounds -> partition set.

Parity: geomesa-fs-storage-common partition schemes (DateTimeScheme,
Z2Scheme/XZ2Scheme, attribute scheme, composite hierarchies) and their
partition-pruning contract (filter -> covered partition list) [upstream,
unverified].

A scheme assigns every feature a partition name (a relative path segment);
`prune` maps extracted query bounds (BBox + Interval) to the set of partition
names that may contain matches — a covering set, possibly `None` meaning
"cannot prune, scan all".
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Set

import numpy as np

from geomesa_tpu.core.columnar import DictColumn, FeatureBatch, GeometryColumn
from geomesa_tpu.cql.extract import BBox, Interval
from geomesa_tpu.curve.z2 import Z2SFC
from geomesa_tpu.curve.xz import XZ2SFC


class PartitionScheme:
    def partitions_for(self, batch: FeatureBatch) -> Sequence[str]:
        """Partition name per feature (len == len(batch))."""
        raise NotImplementedError

    def prune(self, bbox: BBox, interval: Interval) -> Optional[Set[str]]:
        """Covering partition set for the bounds, or None (= all)."""
        raise NotImplementedError

    def to_config(self) -> dict:
        raise NotImplementedError


_DT_PATTERNS: Dict[str, str] = {
    # upstream uses Java DateTimeFormatter patterns; keep the same surface
    "yyyy": "%Y",
    "yyyy/MM": "%Y/%m",
    "yyyy/MM/dd": "%Y/%m/%d",
    "yyyy/MM/dd/HH": "%Y/%m/%d/%H",
    "yyyy/DDD": "%Y/%j",
}

_STEP = {
    "yyyy": "Y",
    "yyyy/MM": "M",
    "yyyy/MM/dd": "D",
    "yyyy/MM/dd/HH": "h",
    "yyyy/DDD": "D",
}


@dataclasses.dataclass
class DateTimeScheme(PartitionScheme):
    """Time-bucketed directories, e.g. 2020/06/01 (pattern yyyy/MM/dd)."""

    pattern: str = "yyyy/MM/dd"
    dtg_attr: str = "dtg"

    def __post_init__(self):
        if self.pattern not in _DT_PATTERNS:
            raise ValueError(
                f"unsupported datetime pattern {self.pattern!r}; "
                f"one of {sorted(_DT_PATTERNS)}"
            )

    def _format(self, millis: np.ndarray) -> np.ndarray:
        """Partition name per timestamp. Each distinct bucket is formatted
        once: a batch spans few buckets and many rows."""
        import datetime as _dt

        fmt = _DT_PATTERNS[self.pattern]
        buckets = np.asarray(millis, np.int64).astype("datetime64[ms]") \
            .astype(f"datetime64[{_STEP[self.pattern]}]")
        uniq, inv = np.unique(buckets, return_inverse=True)
        names = np.array([
            _dt.datetime.fromtimestamp(
                int(b.astype("datetime64[ms]").astype(np.int64)) / 1000,
                _dt.timezone.utc).strftime(fmt)
            for b in uniq
        ], dtype=str)
        return names[inv.reshape(-1)]

    def partitions_for(self, batch: FeatureBatch) -> np.ndarray:
        return self._format(batch.columns[self.dtg_attr])

    def prune(self, bbox: BBox, interval: Interval) -> Optional[Set[str]]:
        if interval.start is None or interval.end is None:
            return None
        step = _STEP[self.pattern]
        t0 = np.datetime64(int(interval.start), "ms").astype(f"datetime64[{step}]")
        t1 = np.datetime64(int(interval.end), "ms").astype(f"datetime64[{step}]")
        bins = np.arange(t0, t1 + np.timedelta64(1, step))
        millis = bins.astype("datetime64[ms]").astype(np.int64)
        return set(self._format(millis))

    def to_config(self):
        return {"scheme": "datetime", "pattern": self.pattern, "dtg": self.dtg_attr}


@dataclasses.dataclass
class Z2Scheme(PartitionScheme):
    """Z2-prefix directories: the top `bits` bits per dimension of the Z2
    curve, e.g. z2/0213 for bits=2 (4^2 cells). Points only."""

    bits: int = 4
    geom_attr: str = "geom"

    def __post_init__(self):
        self._sfc = Z2SFC(self.bits)
        self._digits = max(1, (2 * self.bits + 3) // 4)

    def _name(self, z: np.ndarray) -> List[str]:
        return [f"z2/{int(v):0{self._digits}x}" for v in np.asarray(z).ravel()]

    def partitions_for(self, batch: FeatureBatch) -> List[str]:
        col = batch.columns[self.geom_attr]
        assert isinstance(col, GeometryColumn)
        z = self._sfc.index(col.x, col.y)
        return self._name(z)

    def prune(self, bbox: BBox, interval: Interval) -> Optional[Set[str]]:
        if bbox.is_whole_world:
            return None
        out: Set[str] = set()
        for r in self._sfc.ranges(bbox.xmin, bbox.ymin, bbox.xmax, bbox.ymax,
                                  max_ranges=4 ** self.bits):
            for z in range(r.lower, r.upper + 1):
                out.add(f"z2/{z:0{self._digits}x}")
        return out

    def to_config(self):
        return {"scheme": "z2", "bits": self.bits, "geom": self.geom_attr}


@dataclasses.dataclass
class XZ2Scheme(PartitionScheme):
    """XZ2 sequence-code directories for extended geometries."""

    g: int = 4
    geom_attr: str = "geom"

    def __post_init__(self):
        self._sfc = XZ2SFC(self.g)

    def partitions_for(self, batch: FeatureBatch) -> List[str]:
        col = batch.columns[self.geom_attr]
        assert isinstance(col, GeometryColumn)
        out = []
        if col.is_point:
            for x, y in zip(col.x, col.y):
                out.append(f"xz2/{self._sfc.index(x, y, x, y)}")
        else:
            for i in range(len(col)):
                x0, y0, x1, y1 = col.bbox[i]
                out.append(f"xz2/{self._sfc.index(x0, y0, x1, y1)}")
        return out

    def prune(self, bbox: BBox, interval: Interval) -> Optional[Set[str]]:
        if bbox.is_whole_world:
            return None
        out: Set[str] = set()
        from geomesa_tpu.utils.config import SystemProperties

        budget = int(SystemProperties.SCAN_RANGES_TARGET.get())
        for r in self._sfc.ranges(bbox.xmin, bbox.ymin, bbox.xmax, bbox.ymax,
                                  max_ranges=budget):
            for c in range(r.lower, r.upper + 1):
                out.add(f"xz2/{c}")
        return out

    def to_config(self):
        return {"scheme": "xz2", "g": self.g, "geom": self.geom_attr}


@dataclasses.dataclass
class AttributeScheme(PartitionScheme):
    """One directory per attribute value (dictionary columns only)."""

    attr: str = "type"

    def partitions_for(self, batch: FeatureBatch) -> List[str]:
        col = batch.columns[self.attr]
        assert isinstance(col, DictColumn)
        return [v if v is not None else "__null__" for v in col.decode()]

    def prune(self, bbox: BBox, interval: Interval) -> Optional[Set[str]]:
        return None  # attribute bounds don't flow through BBox/Interval (yet)

    def to_config(self):
        return {"scheme": "attribute", "attr": self.attr}


@dataclasses.dataclass
class CompositeScheme(PartitionScheme):
    """Hierarchical composition: parent/child paths (upstream: composite
    schemes like datetime,z2)."""

    schemes: Sequence[PartitionScheme] = ()

    def partitions_for(self, batch: FeatureBatch) -> List[str]:
        parts = [s.partitions_for(batch) for s in self.schemes]
        return ["/".join(p) for p in zip(*parts)]

    def prune(self, bbox: BBox, interval: Interval) -> Optional[Set[str]]:
        pruned = [s.prune(bbox, interval) for s in self.schemes]
        if all(p is None for p in pruned):
            return None
        # cartesian product of per-level sets; None level = wildcard, which
        # we cannot enumerate, so fall back to prefix filtering by the
        # first non-None levels only
        out: Set[str] = {""}
        for p in pruned:
            if p is None:
                # wildcard: signal prefix-match semantics via trailing '/'
                return {prefix for prefix in out}
            out = {
                (f"{prefix}/{name}" if prefix else name)
                for prefix in out
                for name in p
            }
        return out

    def to_config(self):
        return {"scheme": "composite",
                "schemes": [s.to_config() for s in self.schemes]}


def scheme_from_config(cfg: dict) -> PartitionScheme:
    kind = cfg["scheme"]
    if kind == "datetime":
        return DateTimeScheme(cfg.get("pattern", "yyyy/MM/dd"), cfg.get("dtg", "dtg"))
    if kind == "z2":
        return Z2Scheme(cfg.get("bits", 4), cfg.get("geom", "geom"))
    if kind == "xz2":
        return XZ2Scheme(cfg.get("g", 4), cfg.get("geom", "geom"))
    if kind == "attribute":
        return AttributeScheme(cfg.get("attr", "type"))
    if kind == "composite":
        return CompositeScheme([scheme_from_config(s) for s in cfg["schemes"]])
    raise ValueError(f"unknown partition scheme {kind!r}")
