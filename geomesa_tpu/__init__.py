"""geomesa_tpu — a TPU-native geospatial analytics framework.

A brand-new, columnar, Arrow-first re-design of GeoMesa's capabilities
(reference: nstires-boundless/geomesa; upstream locationtech/geomesa) for
JAX/XLA/Pallas on TPU:

- ``core``    — SimpleFeatureType schemas, columnar feature batches, Arrow IO
                (semantic parity with geomesa-utils SimpleFeatureTypes and
                geomesa-arrow SimpleFeatureVector).
- ``curve``   — Z2/Z3/XZ2/XZ3 space-filling curves, BinnedTime, range
                decomposition (parity with geomesa-z3 org.locationtech.geomesa.curve
                and the sfcurve dependency).
- ``cql``     — ECQL parser, filter analysis (geometry/interval extraction) and
                a predicate compiler to jitted mask functions (parity with
                geomesa-filter FastFilterFactory/FilterHelper).
- ``store``   — filesystem (Parquet) datastore with partition schemes and
                pruning (parity with geomesa-fs), plus a device cache manager.
- ``engine``  — the TPU kernel suite replacing server-side iterator scans
                (geomesa-index-api iterators: DensityScan, ArrowScan, BinScan,
                StatsScan) and process hot loops: filter masks, point-in-polygon,
                haversine kNN, density scatter, tube-select, stats reductions.
- ``plan``    — query planner, hints, explain, audit (parity with
                geomesa-index-api planning: QueryPlanner, QueryHints, Explainer).
- ``process`` — analytics process library (parity with geomesa-process):
                KNN, Density, TubeSelect, Proximity, Unique, Stats, Sampling...
- ``convert`` — converter-lite ingest framework (parity with geomesa-convert).
- ``stats``   — mergeable stat sketches + Stat DSL (parity with geomesa-utils
                org.locationtech.geomesa.utils.stats).
- ``security``— visibility expressions (parity with geomesa-security).
- ``faults``  — fault-injection harness (named sites at every dependency
                boundary, seeded replayable FaultPlans) + the recovery
                fabric: typed error classification, deadline-aware retry with
                full-jitter backoff, per-dependency circuit breakers,
                device-OOM host-eval fallback, poison-query quarantine,
                and the ``gmtpu chaos`` invariant gate (no upstream
                analog; docs/ROBUSTNESS.md).
- ``cli``     — command-line tools (parity with geomesa-tools).

Parallelism: feature batches shard over a ``jax.sharding.Mesh`` axis "shard";
aggregations merge with XLA collectives (psum / all_gather / ring top-k over
ICI) — the TPU-native replacement for Accumulo/HBase server-side fan-in.
"""

__version__ = "0.1.0"

from geomesa_tpu.core.sft import SimpleFeatureType, AttributeDescriptor
from geomesa_tpu.core.columnar import FeatureBatch

__all__ = [
    "SimpleFeatureType",
    "AttributeDescriptor",
    "FeatureBatch",
    "__version__",
]
