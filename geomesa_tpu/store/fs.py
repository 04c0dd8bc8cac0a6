"""Filesystem (Parquet) storage: partitioned writes, pruned + pushed-down reads.

Parity: geomesa-fs-storage-parquet SimpleFeatureParquetWriter + FilterConverter
(CQL -> Parquet predicate pushdown) and geomesa-fs-datastore's
query = prune partitions -> read files w/ pushdown -> residual pipeline
[upstream, unverified].

Layout on disk:

    <root>/metadata.json            sft spec + scheme config + manifest
    <root>/<partition>/<uuid>.parquet

Parquet schema is the flat columnar mapping of core.arrow_io (point geometry
as x/y float64 columns named <attr>__x/__y so min/max row-group statistics
prune on bbox; extended geometries as WKT plus <attr>__bbox_* bound columns).
Partition pruning consumes the covering sets from store.partition; pruned
names match partitions by exact name or path-prefix (composite wildcards).
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import uuid
from typing import Dict, Iterator, List, Optional, Sequence, Set

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

from geomesa_tpu.core.columnar import DictColumn, FeatureBatch, GeometryColumn
from geomesa_tpu.core.sft import SimpleFeatureType
from geomesa_tpu.core.wkt import parse_wkt, to_wkt
from geomesa_tpu.cql.extract import BBox, Interval
from geomesa_tpu.faults import BREAKERS, RetryPolicy, retry_call
from geomesa_tpu.faults import harness as _faults
from geomesa_tpu.store.partition import PartitionScheme, scheme_from_config

METADATA = "metadata.json"
FID = "__fid__"

# fault-injection sites + retry policy for the storage boundary
# (docs/ROBUSTNESS.md). Reads and partition-file writes retry transient
# I/O against the "storage" breaker. The manifest commit is DELIBERATELY
# non-retryable: it runs under the manifest lock (sleeping there stalls
# every reader/writer) and the tmp+os.replace swap is already
# all-or-nothing — a failed commit leaves the previous manifest intact,
# never a torn one (.gmtpu-waivers documents this contract).
_READ_SITE = _faults.site(
    "fs.read_partition", "partition data file read (parquet/orc)")
_WRITE_SITE = _faults.site(
    "fs.write_partition", "partition data file write (staging)")
_MANIFEST_SITE = _faults.site(
    "fs.write_manifest", "metadata.json manifest commit (atomic swap)")
_STORAGE_RETRY = RetryPolicy(max_attempts=4, base_ms=5.0, cap_ms=250.0)


class ManifestSnapshot(Dict[str, List[dict]]):
    """A plain partition->entries dict plus the commit version it was
    taken at (monotonic per storage instance). Every dict consumer works
    unchanged; version-aware consumers use `.version` to refuse applying
    an older snapshot over newer state."""

    version: int = 0


def _batch_to_table(batch: FeatureBatch) -> pa.Table:
    arrays: Dict[str, pa.Array] = {}
    for a in batch.sft.attributes:
        col = batch.columns[a.name]
        if isinstance(col, GeometryColumn):
            if col.is_point:
                arrays[f"{a.name}__x"] = pa.array(col.x, pa.float64())
                arrays[f"{a.name}__y"] = pa.array(col.y, pa.float64())
            else:
                arrays[a.name] = pa.array(
                    [to_wkt(col.geometry(i)) for i in range(len(col))]
                )
                bb = col.bbox
                arrays[f"{a.name}__xmin"] = pa.array(bb[:, 0], pa.float64())
                arrays[f"{a.name}__ymin"] = pa.array(bb[:, 1], pa.float64())
                arrays[f"{a.name}__xmax"] = pa.array(bb[:, 2], pa.float64())
                arrays[f"{a.name}__ymax"] = pa.array(bb[:, 3], pa.float64())
        elif isinstance(col, DictColumn):
            codes = np.asarray(col.codes, np.int64)
            arrays[a.name] = pa.DictionaryArray.from_arrays(
                pa.array(codes, pa.int32(), mask=codes < 0),
                pa.array(col.vocab, pa.string()),
            )
        elif a.type == "Bytes":
            arrays[a.name] = pa.array(list(col), pa.binary())
        elif a.is_temporal:
            arrays[a.name] = pa.array(np.asarray(col, np.int64), pa.int64())
        else:
            arrays[a.name] = pa.array(col)
    if batch.fids is not None:
        codes = np.asarray(batch.fids.codes, np.int64)
        arrays[FID] = pa.DictionaryArray.from_arrays(
            pa.array(codes, pa.int32(), mask=codes < 0),
            pa.array(batch.fids.vocab, pa.string()),
        )
    return pa.table(arrays)


def _table_to_batch(t: pa.Table, sft: SimpleFeatureType) -> FeatureBatch:
    # projection support: narrow the SFT to the attributes present
    present = [
        a
        for a in sft.attributes
        if (a.name in t.schema.names)
        or (a.is_geometry and a.type == "Point" and f"{a.name}__x" in t.schema.names)
    ]
    if len(present) != len(sft.attributes):
        sft = SimpleFeatureType(sft.name, present, sft.user_data)
    cols: Dict[str, object] = {}
    for a in sft.attributes:
        if a.is_geometry:
            if a.type == "Point":
                x = t.column(f"{a.name}__x").to_numpy()
                y = t.column(f"{a.name}__y").to_numpy()
                cols[a.name] = GeometryColumn.from_points(x, y)
            else:
                geoms = [parse_wkt(w) for w in t.column(a.name).to_pylist()]
                cols[a.name] = GeometryColumn.from_geometries(geoms)
        elif a.type in ("String", "UUID"):
            col = t.column(a.name)
            arr = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
            if pa.types.is_dictionary(arr.type):
                codes = arr.indices.to_numpy(zero_copy_only=False)
                if codes.dtype.kind == "f":
                    codes = np.where(np.isnan(codes), -1, codes)
                cols[a.name] = DictColumn(codes.astype(np.int32), arr.dictionary.to_pylist())
            else:
                cols[a.name] = DictColumn.encode(arr.to_pylist())
        elif a.type == "Bytes":
            cols[a.name] = np.array(t.column(a.name).to_pylist(), dtype=object)
        else:
            cols[a.name] = t.column(a.name).to_numpy()
    fids = None
    if FID in t.schema.names:
        col = t.column(FID)
        arr = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
        if pa.types.is_dictionary(arr.type):
            codes = arr.indices.to_numpy(zero_copy_only=False)
            if codes.dtype.kind == "f":
                codes = np.where(np.isnan(codes), -1, codes)
            fids = DictColumn(codes.astype(np.int32), arr.dictionary.to_pylist())
        else:
            fids = DictColumn.encode(arr.to_pylist())
    return FeatureBatch(sft, cols, fids)


class FileSystemStorage:
    """A partitioned Parquet (or ORC) feature store."""

    def __init__(
        self,
        root: str,
        sft: SimpleFeatureType,
        scheme: PartitionScheme,
        encoding: str = "parquet",
    ):
        if encoding not in ("parquet", "orc"):
            raise ValueError(f"unknown encoding {encoding!r}")
        self.root = root
        self.sft = sft
        self.scheme = scheme
        self.encoding = encoding
        # manifest: partition -> list of {"file", "count"}
        self.manifest: Dict[str, List[dict]] = {}
        # serve made writer-vs-scan concurrency the normal mode: without
        # this, _save_metadata can crash iterating the manifest mid-append
        # ("dictionary changed size") and readers see torn entry lists.
        # Data files are immutable once written, so only manifest state
        # needs the lock — file I/O stays outside it. The version bumps
        # on every committed mutation so consumers can order snapshots
        # (DeviceCacheManager refuses to roll residency backward).
        self._lock = threading.Lock()
        self._mversion = 0

    # -- lifecycle ---------------------------------------------------------

    @classmethod
    def create(
        cls,
        root: str,
        sft: SimpleFeatureType,
        scheme: PartitionScheme,
        encoding: str = "parquet",
    ) -> "FileSystemStorage":
        os.makedirs(root, exist_ok=True)
        if os.path.exists(os.path.join(root, METADATA)):
            raise FileExistsError(f"storage already exists at {root}")
        store = cls(root, sft, scheme, encoding)
        store._save_metadata()
        return store

    @classmethod
    def load(cls, root: str) -> "FileSystemStorage":
        with open(os.path.join(root, METADATA)) as f:
            meta = json.load(f)
        sft = SimpleFeatureType.from_spec(meta["name"], meta["spec"])
        store = cls(
            root,
            sft,
            scheme_from_config(meta["scheme"]),
            meta.get("encoding", "parquet"),
        )
        store.manifest = meta.get("manifest", {})
        return store

    def _save_metadata(self):
        """Persist metadata + manifest. Callers on the mutation paths
        hold self._lock so the json serialization sees one consistent
        manifest (a concurrent append would otherwise blow up the dict
        iteration); `create` runs before the store is shared."""
        from geomesa_tpu.parallel.distributed import is_coordinator

        if not is_coordinator():
            # multi-host runtimes READ the FS store (each host feeds
            # from its process_partitions slice); mutation is single-
            # writer before serving. The gate keeps a non-coordinator
            # host from clobbering the shared manifest with its
            # partial view of the partition set (GT27)
            return
        meta = {
            "version": 1,
            "name": self.sft.name,
            "spec": self.sft.to_spec(),
            "scheme": self.scheme.to_config(),
            "encoding": self.encoding,
            "manifest": self.manifest,
        }
        tmp = os.path.join(self.root, METADATA + ".tmp")
        # injection point for the chaos harness: a failure HERE (before
        # or during the tmp write) must leave the previous manifest
        # untouched — the no-torn-manifest invariant gmtpu chaos checks
        _MANIFEST_SITE.fire()
        # gt: waive GT09
        # (deliberate: persisting under the manifest lock is the point —
        # the snapshot must not move while it serializes; the final
        # os.replace swap is atomic for readers of the file)
        with open(tmp, "w") as f:
            json.dump(meta, f, indent=1)
        os.replace(tmp, os.path.join(self.root, METADATA))

    @property
    def count(self) -> int:
        with self._lock:
            return sum(f["count"]
                       for files in self.manifest.values() for f in files)

    # -- write -------------------------------------------------------------

    def write(self, batch: FeatureBatch) -> None:
        """Partition the batch by the scheme and append one parquet file per
        touched partition. Writes are idempotent at file granularity (fresh
        uuids), matching the reference's append model."""
        if batch.valid is not None and not batch.valid.all():
            batch = batch.select(batch.valid)
        names = np.asarray(self.scheme.partitions_for(batch))
        # stage every partition file FIRST (outside the lock), then
        # commit the whole batch to the manifest in ONE lock acquisition:
        # a concurrent reader snapshot sees all of this write or none of
        # it, so counts only ever move at batch boundaries (the serve
        # torn-read contract, tests/test_serve_concurrency.py)
        staged = []
        uniq, inv = np.unique(names, return_inverse=True)
        inv = inv.reshape(-1)
        # rows grouped by partition, in batch order within each
        order = np.argsort(inv, kind="stable")
        ends = np.cumsum(np.bincount(inv, minlength=len(uniq)))
        for name, lo, hi in zip(uniq, np.r_[0, ends[:-1]], ends):
            sub = batch.select(order[lo:hi])
            pdir = os.path.join(self.root, name)
            os.makedirs(pdir, exist_ok=True)
            fname = f"{uuid.uuid4().hex}.{self.encoding}"
            # retryable: the file is not in the manifest yet, so a
            # partial write from a failed attempt is an invisible
            # orphan the successful attempt simply overwrites
            retry_call(
                self._write_data_file, sub, os.path.join(pdir, fname),
                policy=_STORAGE_RETRY, label="storage",
                breaker=BREAKERS.get("storage"))
            staged.append((str(name), fname, len(sub)))
        with self._lock:
            for name, fname, count in staged:
                self.manifest.setdefault(name, []).append(
                    {"file": fname, "count": count}
                )
            try:
                self._save_metadata()
            except BaseException:
                # the durable commit failed: ROLL BACK the in-memory
                # append so memory never runs ahead of disk — otherwise
                # this "failed" batch would keep serving from memory, a
                # client retry would duplicate every row, and the next
                # unrelated write would silently commit it. We hold the
                # lock for the whole append+save, so our entries are
                # still the tail of each partition list; the staged
                # files become unreferenced orphans (harmless).
                for name, fname, count in staged:
                    entries = self.manifest.get(name, [])
                    if entries and entries[-1].get("file") == fname:
                        entries.pop()
                    if not entries:
                        self.manifest.pop(name, None)
                raise
            self._mversion += 1

    def compact(self, partition: Optional[str] = None) -> int:
        """Merge each touched partition's files into one (the FS store's
        compact command). Returns how many files were removed."""
        with self._lock:
            targets = [partition] if partition is not None \
                else list(self.manifest)
        removed = 0
        for name in targets:
            with self._lock:
                entries = list(self.manifest.get(name, []))
            if len(entries) <= 1:
                continue
            tables = []
            for entry in entries:
                path = os.path.join(self.root, name, entry["file"])
                tables.append(self._read_file(path, None, None))
            merged = pa.concat_tables(tables, promote_options="permissive")
            count = sum(e["count"] for e in entries)
            fname = f"{uuid.uuid4().hex}.{self.encoding}"
            out = os.path.join(self.root, name, fname)
            retry_call(self._write_table, merged, out,
                       policy=_STORAGE_RETRY, label="storage",
                       breaker=BREAKERS.get("storage"))
            # crash-safety ordering: write merged file, point the manifest
            # at it, persist — only then delete the old files. A crash
            # leaves either the old manifest (old files intact) or the new
            # one (merged file intact); never a manifest of missing files.
            with self._lock:
                # writes only APPEND, so the snapshot is a prefix of the
                # live list: keep any entry a concurrent write() added
                # since (wholesale replace would orphan its file/rows)
                prev = self.manifest.get(name)
                tail = self.manifest.get(name, [])[len(entries):]
                self.manifest[name] = [{"file": fname,
                                        "count": count}] + tail
                try:
                    self._save_metadata()
                except BaseException:
                    # memory must never run ahead of the durable
                    # manifest (same rollback as write/delete): restore
                    # the live pre-compact list — the merged file
                    # becomes an unreferenced orphan, the old files
                    # stay live and are NOT removed below
                    if prev is not None:
                        self.manifest[name] = prev
                    else:  # pragma: no cover - entries implied a list
                        self.manifest.pop(name, None)
                    raise
                self._mversion += 1
            for entry in entries:
                os.remove(os.path.join(self.root, name, entry["file"]))
                removed += 1
        return removed

    def delete_features(self, cql: "str | object") -> int:
        """Delete features matching an ECQL filter (geomesa-tools
        delete-features; upstream writes deletion mutations — here each
        touched file is rewritten without the matching rows). Exact f64
        host evaluation; crash-safety ordering as in compact (new file +
        manifest first, removals last). Returns rows deleted."""
        from geomesa_tpu.cql import ast, parse_cql
        from geomesa_tpu.cql.hosteval import eval_filter_host

        f = parse_cql(cql) if isinstance(cql, str) else cql
        if isinstance(f, ast.Include):
            # delete-all: clear every partition (schema stays). Same
            # crash-safety ordering as below: persist the emptied
            # manifest FIRST, remove files last — a crash then leaves
            # either the old manifest (files intact) or the new one
            # (orphaned files, harmless), never references to missing
            # files.
            total = self.count
            with self._lock:
                paths = [
                    os.path.join(self.root, name, entry["file"])
                    for name, entries in self.manifest.items()
                    for entry in entries
                ]
                prev = self.manifest
                self.manifest = {}
                try:
                    self._save_metadata()
                except BaseException:
                    # memory must never run ahead of the durable
                    # manifest (same invariant as write()'s rollback)
                    self.manifest = prev
                    raise
                self._mversion += 1
            for p in paths:
                os.remove(p)
            return total
        deleted = 0
        with self._lock:
            names = list(self.manifest)
        for name in names:
            new_entries = []
            removals = []
            changed = False
            with self._lock:
                entries = list(self.manifest.get(name, []))
            for entry in entries:
                path = os.path.join(self.root, name, entry["file"])
                batch = _table_to_batch(
                    self._read_file(path, None, None), self.sft)
                hit = eval_filter_host(f, batch)
                nh = int(hit.sum())
                if nh == 0:
                    new_entries.append(entry)
                    continue
                changed = True
                deleted += nh
                removals.append(entry["file"])
                keep = batch.select(~hit)
                if len(keep):
                    fname = f"{uuid.uuid4().hex}.{self.encoding}"
                    out = os.path.join(self.root, name, fname)
                    retry_call(self._write_data_file, keep, out,
                               policy=_STORAGE_RETRY, label="storage",
                               breaker=BREAKERS.get("storage"))
                    new_entries.append({"file": fname, "count": len(keep)})
            if changed:
                with self._lock:
                    # preserve entries a concurrent write() appended
                    # after our snapshot (appends-only: snapshot is a
                    # prefix of the live list)
                    prev = self.manifest.get(name)
                    tail = self.manifest.get(name, [])[len(entries):]
                    if new_entries or tail:
                        self.manifest[name] = new_entries + tail
                    else:
                        del self.manifest[name]
                    try:
                        self._save_metadata()
                    except BaseException:
                        # roll back: a failed durable commit must not
                        # leave the deletion visible in memory (phantom
                        # deletes that a restart would resurrect)
                        if prev is not None:
                            self.manifest[name] = prev
                        else:
                            self.manifest.pop(name, None)
                        raise
                    self._mversion += 1
                for fname in removals:
                    os.remove(os.path.join(self.root, name, fname))
        return deleted

    def age_off(self, older_than_ms: int, dtg_attr: "str | None" = None) -> int:
        """Delete features whose dtg is strictly before `older_than_ms`
        (the FS analog of the KV store's age-off; upstream: the age-off
        iterators/filters). Returns rows deleted."""
        from geomesa_tpu.cql import ast

        d = (self.sft.attribute(dtg_attr) if dtg_attr
             else self.sft.default_dtg)
        if d is None:
            raise ValueError("age_off needs a dtg attribute")
        return self.delete_features(
            ast.TemporalPredicate(
                "BEFORE", ast.Property(d.name), int(older_than_ms), None)
        )

    # -- read --------------------------------------------------------------

    def manifest_snapshot(self) -> "ManifestSnapshot":
        """One consistent view of partition -> entry list, taken in a
        single lock acquisition, stamped with the commit version.
        Queries that enumerate partitions and then read their files must
        do BOTH against the same snapshot, or a concurrent batch-atomic
        write tears across the two reads (new rows visible in old
        partitions, new partitions missing)."""
        with self._lock:
            snap = ManifestSnapshot(
                (name, list(entries))
                for name, entries in self.manifest.items())
            snap.version = self._mversion
            return snap

    def manifest_version(self) -> int:
        """The current committed write version (monotonic per
        instance) without copying the manifest — the serve result
        cache's peek-time key component (geomesa_tpu.approx.cache)."""
        with self._lock:
            return self._mversion

    def partitions(self) -> List[str]:
        with self._lock:
            return sorted(self.manifest)

    def prune_partitions(self, bbox: BBox, interval: Interval,
                         manifest: Optional[Dict[str, List[dict]]] = None,
                         ) -> List[str]:
        names = (sorted(manifest) if manifest is not None
                 else self.partitions())
        pruned = self.scheme.prune(bbox, interval)
        if pruned is None:
            return names
        out = []
        for name in names:
            for p in pruned:
                if name == p or name.startswith(p + "/") or p == "":
                    out.append(name)
                    break
        return sorted(out)

    def _pushdown_expr(self, bbox: BBox, interval: Interval):
        """Build a pyarrow filter expression from the covering bounds —
        the FilterConverter analog (row-group statistics do the pruning)."""
        g = self.sft.default_geometry
        d = self.sft.default_dtg
        expr = None

        def AND(a, b):
            return b if a is None else (a if b is None else a & b)

        if g is not None and not bbox.is_whole_world:
            if g.type == "Point":
                e = (
                    (pc.field(f"{g.name}__x") >= bbox.xmin)
                    & (pc.field(f"{g.name}__x") <= bbox.xmax)
                    & (pc.field(f"{g.name}__y") >= bbox.ymin)
                    & (pc.field(f"{g.name}__y") <= bbox.ymax)
                )
            else:
                e = (
                    (pc.field(f"{g.name}__xmin") <= bbox.xmax)
                    & (pc.field(f"{g.name}__xmax") >= bbox.xmin)
                    & (pc.field(f"{g.name}__ymin") <= bbox.ymax)
                    & (pc.field(f"{g.name}__ymax") >= bbox.ymin)
                )
            expr = AND(expr, e)
        if d is not None and not interval.is_unbounded:
            if interval.start is not None:
                expr = AND(expr, pc.field(d.name) >= int(interval.start))
            if interval.end is not None:
                expr = AND(expr, pc.field(d.name) <= int(interval.end))
        return expr

    def scan(
        self,
        bbox: Optional[BBox] = None,
        interval: Optional[Interval] = None,
        columns: Optional[Sequence[str]] = None,
    ) -> Iterator[FeatureBatch]:
        """Yield batches from pruned partitions with parquet pushdown.

        The result is a *covering* superset: exact predicate evaluation is
        the engine's job (residual mask), same as the reference's split.
        """
        bbox = bbox if bbox is not None else BBox(-180.0, -90.0, 180.0, 90.0)
        interval = interval if interval is not None else Interval(None, None)
        expr = self._pushdown_expr(bbox, interval)
        phys_cols = None
        if columns is not None:
            phys_cols = []
            for c in columns:
                a = self.sft.attribute(c)
                if a.is_geometry and a.type == "Point":
                    phys_cols += [f"{c}__x", f"{c}__y"]
                elif a.is_geometry:
                    phys_cols += [c, f"{c}__xmin", f"{c}__ymin", f"{c}__xmax", f"{c}__ymax"]
                else:
                    phys_cols.append(c)
        # one snapshot for BOTH pruning and entry reads: a batch-atomic
        # concurrent write is either fully visible or not at all
        snap = self.manifest_snapshot()
        for name in self.prune_partitions(bbox, interval, manifest=snap):
            for entry in snap.get(name, []):
                path = os.path.join(self.root, name, entry["file"])
                cols = phys_cols
                if phys_cols is not None:
                    # include fids only when the file actually has them
                    schema_names = self._file_schema_names(path)
                    cols = phys_cols + ([FID] if FID in schema_names else [])
                # geomesa.scan.batch.size bounds per-yield rows so one huge
                # file cannot force an oversized host allocation — and the
                # parquet path STREAMS row groups (pads.Scanner.to_batches)
                # so consumers can overlap decode with device compute (the
                # cold-path pipeline; the whole file is never materialized)
                from geomesa_tpu.utils.config import SystemProperties

                target = int(SystemProperties.SCAN_BATCH_SIZE.get())
                for t in self._stream_file(path, expr, cols, target):
                    if len(t):
                        yield _table_to_batch(t, self.sft)

    def scan_partitions(
        self,
        names: Sequence[str],
        manifest: Optional[Dict[str, List[dict]]] = None,
    ) -> Iterator[FeatureBatch]:
        """Yield every row (all columns) of the named partitions, no
        pushdown — the device-cache residency read (store.cache and the
        export jobs load whole partitions). Passing a `manifest`
        snapshot pins the read to one committed write version."""
        snap = manifest if manifest is not None else self.manifest_snapshot()
        for name in names:
            for entry in snap.get(name, []):
                path = os.path.join(self.root, name, entry["file"])
                t = self._read_file(path, None, None)
                if len(t):
                    yield _table_to_batch(t, self.sft)

    def _write_data_file(self, sub: FeatureBatch, path: str) -> None:
        """Encode + write one partition data file (the staged half of a
        batch-atomic write). A distinct method so the retry fabric can
        re-attempt the WHOLE encode+write as one idempotent unit."""
        self._write_table(_batch_to_table(sub), path)

    def _write_table(self, table: pa.Table, path: str) -> None:
        _WRITE_SITE.fire()
        if self.encoding == "orc":
            from pyarrow import orc

            orc.write_table(self._decode_dictionaries(table), path,
                            compression="zstd")
        else:
            pq.write_table(table, path, compression="zstd",
                           row_group_size=64 * 1024)

    @staticmethod
    def _decode_dictionaries(table: pa.Table) -> pa.Table:
        """ORC has no dictionary type: cast dict columns to their value
        type (the read path re-encodes into DictColumn)."""
        fields = []
        arrays = []
        for field in table.schema:
            col = table.column(field.name)
            if pa.types.is_dictionary(field.type):
                col = col.cast(field.type.value_type)
                field = pa.field(field.name, field.type.value_type)
            fields.append(field)
            arrays.append(col)
        return pa.Table.from_arrays(arrays, schema=pa.schema(fields))

    def _file_schema_names(self, path: str) -> List[str]:
        if self.encoding == "orc":
            from pyarrow import orc

            return orc.ORCFile(path).schema.names
        return pq.read_schema(path).names

    def _read_file(self, path: str, expr, cols):
        """Read one data file with predicate + column pushdown. Parquet uses
        row-group statistics natively; ORC goes through pyarrow.dataset for
        stripe-level filtering (the geomesa-fs-storage-orc analog).
        Transient read failures retry against the storage breaker —
        data files are immutable once committed, so a re-read is
        trivially idempotent."""
        return retry_call(
            self._read_file_once, path, expr, cols,
            policy=_STORAGE_RETRY, label="storage",
            breaker=BREAKERS.get("storage"))

    def _read_file_once(self, path: str, expr, cols):
        _READ_SITE.fire()
        if self.encoding == "orc":
            import pyarrow.dataset as pads

            dataset = pads.dataset(path, format="orc")
            return dataset.to_table(filter=expr, columns=cols)
        return pq.read_table(path, filters=expr, columns=cols)

    def _stream_file(self, path: str, expr, cols, target: int):
        """Yield ~target-row pyarrow Tables from one file incrementally.
        Parquet decodes row-group-wise with predicate+column pushdown;
        ORC falls back to a whole-file read chunked afterwards. Only the
        dataset/scanner OPEN retries: a failure mid-stream surfaces
        typed instead of replaying already-yielded rows (documented
        non-retryable case, docs/ROBUSTNESS.md)."""
        if self.encoding == "orc":
            t = self._read_file(path, expr, cols)
            for off in range(0, max(len(t), 1), target):
                yield t.slice(off, target)
            return
        import pyarrow as pa

        def _open():
            import pyarrow.dataset as pads

            _READ_SITE.fire()
            return pads.dataset(path, format="parquet").scanner(
                filter=expr, columns=cols, batch_size=target
            )

        scanner = retry_call(
            _open, policy=_STORAGE_RETRY, label="storage",
            breaker=BREAKERS.get("storage"))
        pending = []
        rows = 0
        for rb in scanner.to_batches():
            while rb.num_rows:
                take = min(rb.num_rows, target - rows)
                pending.append(rb.slice(0, take))
                rb = rb.slice(take)
                rows += take
                if rows >= target:  # hard per-yield bound (SCAN_BATCH_SIZE)
                    yield pa.Table.from_batches(pending)
                    pending, rows = [], 0
        if pending:
            yield pa.Table.from_batches(pending)

    def read_all(self) -> Optional[FeatureBatch]:
        batches = list(self.scan())
        return FeatureBatch.concat(batches) if batches else None
