"""Device cache manager: HBM residency with a persistent manifest.

Parity: SURVEY.md §5.4's checkpoint/resume obligation — the reference's
"checkpointing" is FS partition->file manifests + Kafka offsets; the TPU
analog is a manifest of *device residency*: which partition files are
resident in HBM, under which layout version, so a restarted server rebuilds
identical device state deterministically. Also covers the Kafka-layer
snapshot-refresh design (SURVEY.md C12 TPU note): `refresh()` is the
double-buffered snapshot swap — a new padded batch is built while the old
one keeps serving, then the reference flips.

Layout notes:
- partitions are cached independently (pruning stays effective: a query
  touching 3 of 300 partitions pulls 3 cache entries);
- each entry is padded to the next pow2 so jit cache keys stabilize across
  refreshes (same policy as the planner's scan path);
- LAYOUT_VERSION participates in the manifest: a layout change invalidates
  stale residency on load instead of serving mis-shaped arrays.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from geomesa_tpu.core.columnar import FeatureBatch
from geomesa_tpu.store.fs import FileSystemStorage
from geomesa_tpu.utils.padding import next_pow2 as _next_pow2

LAYOUT_VERSION = 1
MANIFEST = ".device_cache.json"


def _locked(fn):
    """Serialize a DeviceCacheManager method on the instance RLock —
    ensure/refresh/invalidate/superbatch are compound read-modify-write
    sequences that tear under concurrent queries without it."""

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self._lock:
            return fn(self, *args, **kwargs)

    return wrapper


@dataclasses.dataclass
class CacheEntry:
    """One resident partition (host columnar copy; device residency lives
    in the concatenated superbatch — see `superbatch()`)."""

    files: List[str]  # source files (residency provenance)
    count: int  # valid rows
    padded: int  # padded device length (pow2)
    batch: FeatureBatch  # host copy (padded)
    dev: Optional[dict] = None  # per-partition device segment (flat stores)


@dataclasses.dataclass
class SuperBatch:
    """All resident partitions as ONE device batch + a partition-id row
    column. Execution masks pruned-out partitions by lane (allowed[pid])
    instead of dispatching per-partition kernels: with a device round
    trip and a kernel launch per partition, one dense pass over every
    resident row beats dozens of tiny dispatches —
    partition pruning still governs what gets LOADED into HBM."""

    batch: FeatureBatch          # host concat (padded segments)
    dev: dict                    # DeviceBatch of the concat
    pids: object                 # device i32 [N] partition id per row
    ids: Dict[str, int]          # partition name -> id
    version: int
    # mesh residency tier (docs/SERVING.md "Sharded serving"): when the
    # cache carries a serving mesh, `dev` arrays are NamedSharding-placed
    # over it (feature axis sharded, CSR/replicated keys replicated) and
    # the layout is the SERIAL layout plus trailing invalid padding to a
    # multiple of the mesh size — so global row indices (and therefore
    # kNN results) are bit-identical to the single-chip path. `owners`
    # records per-chip tile ownership: which shards hold each
    # partition's rows — the shard-affinity signal admission and the
    # planner's dispatch route consume.
    mesh: object = None                       # jax.sharding.Mesh | None
    shard_rows: int = 0                       # rows per shard (mesh only)
    owners: Dict[str, tuple] = dataclasses.field(default_factory=dict)

    def shards_for(self, partitions) -> tuple:
        """Sorted shard ids owning any of `partitions`' rows (empty
        tuple when the cache is single-chip or nothing matches)."""
        out: set = set()
        for name in partitions:
            out.update(self.owners.get(name, ()))
        return tuple(sorted(out))

    # Round-3: residency changes no longer re-upload unchanged segments
    # for FLAT stores (point geometry + numeric/date/dict columns): each
    # partition keeps its own device segment, dictionary columns are
    # re-encoded against a store-level grow-only vocab at load time (so
    # device codes stay comparable across partitions), and the superbatch
    # is a DEVICE-side concat of segments. Non-point geometry (CSR ring
    # tables need offset rewrites on concat) falls back to the round-1
    # full host-concat + re-upload. Host RAM still holds per-partition
    # copies for the double-buffered reload path.


class DeviceCacheManager:
    """Keeps partitions of a FileSystemStorage resident on device."""

    def __init__(self, storage: FileSystemStorage, coord_dtype=None,
                 mesh=None):
        self.storage = storage
        self.coord_dtype = coord_dtype
        # serving mesh (docs/SERVING.md "Sharded serving"): when set,
        # superbatch() builds the mesh-resident tier — one
        # NamedSharding upload per residency change (per manifest
        # snapshot, never per query) with per-chip row-range ownership.
        # Extended-geometry stores stay single-chip: their CSR ring
        # tables index per-feature arrays, which row sharding would
        # misalign.
        self.mesh = mesh
        # reentrant: compound ops (refresh -> ensure, resume -> _load)
        # re-enter; guards every mutation/compound read so concurrent
        # queries (the serve dispatch thread) never observe a half-swapped
        # superbatch or race an invalidating writer
        self._lock = threading.RLock()
        self._entries: Dict[str, CacheEntry] = {}
        self._super: Optional[SuperBatch] = None
        self._version = 0
        self._applied_mversion = -1  # storage commit version last applied
        # store-level grow-only vocabularies (per dict column) so device
        # code segments from different partitions remain comparable
        self._vocab: Dict[str, list] = {}
        self.upload_count = 0  # partitions transferred host->device
        # host->device transfer accounting (ROADMAP item 4 foundation):
        # rows that actually crossed host->device. The incremental mesh
        # GROWTH path appends only the delta tile, so these counters
        # must NOT scale with resident size on append — regression-
        # asserted in tests/test_device_cache.py
        self.upload_rows = 0
        # last mesh superbatch layout, kept for the delta-append path:
        # (mesh, names tuple, {name: (padded, files tuple)}, concat row
        # count BEFORE mesh padding, dev dict, padded_total)
        self._mesh_prev = None
        self._flat = all(
            (not a.is_geometry) or a.type == "Point"
            for a in storage.sft.attributes
        )

    # -- mesh residency (docs/SERVING.md "Sharded serving") ----------------

    def _mesh_active(self) -> bool:
        return self.mesh is not None and self._flat

    @_locked
    def serving_mesh(self):
        """The mesh live dispatch will actually take: the installed
        mesh when the mesh residency tier is active (flat store), else
        None. The pipeline keys its staging placement on THIS — not on
        `ServeConfig.mesh` — so a store the tier cannot shard
        (extended geometry, or no device cache at all) stages
        single-device buffers for the single-chip kernel it will
        actually run."""
        return self.mesh if self._mesh_active() else None

    @_locked
    def set_mesh(self, mesh) -> None:
        """Install (or clear) the serving mesh. Residency is rebuilt on
        the next superbatch(): entries keep their host copies; stale
        single-device segments are dropped so the sharded upload does
        not double HBM. No-op when the mesh is unchanged — by VALUE:
        every QueryService construction resolves a fresh Mesh object
        over the same devices (serve_mesh), and dropping residency on
        an identical placement would re-upload the whole store for
        nothing."""
        if mesh is self.mesh or (
                mesh is not None and self.mesh is not None
                and mesh == self.mesh):
            return
        self.mesh = mesh
        if self._mesh_active():
            for e in self._entries.values():
                e.dev = None
        self._super = None
        self._mesh_prev = None  # layout-invalidating: full re-tier
        self._version += 1
        # flight-recorder lifecycle event (docs/OBSERVABILITY.md): a
        # re-tier drops residency and re-uploads on the next
        # superbatch — a crash dump that shows one right before a
        # latency cliff explains a multi-chip incident by itself
        from geomesa_tpu.telemetry.recorder import RECORDER

        RECORDER.note_event(
            "mesh", action="retier",
            shape=(list(int(s) for s in mesh.devices.shape)
                   if mesh is not None else None),
            entries=len(self._entries))

    @_locked
    def shards_for(self, partitions) -> tuple:
        """Shard-affinity lookup: the sorted shard ids owning the named
        partitions' rows under the CURRENT mesh superbatch. PEEK-only —
        a cold or stale cache answers () instead of paying a residency
        build on the caller's (admission) thread; the planner's mesh
        dispatch reads ownership off the superbatch it just ensured."""
        if not self._mesh_active() or self._super is None:
            return ()
        return self._super.shards_for(partitions)

    # -- residency ---------------------------------------------------------

    def _partition_files(self, name: str,
                         manifest: Optional[dict] = None) -> List[str]:
        src = manifest if manifest is not None else self.storage.manifest
        return sorted(e["file"] for e in src.get(name, []))

    def _shared_vocab_recode(self, batch: FeatureBatch) -> FeatureBatch:
        """Re-encode dict columns against the store-level vocabularies
        (append-only merge) so per-partition device code segments are
        directly concatenable."""
        from geomesa_tpu.core.columnar import DictColumn

        cols = dict(batch.columns)
        changed = False
        for name, col in batch.columns.items():
            if not isinstance(col, DictColumn):
                continue
            vocab = self._vocab.setdefault(name, [])
            lookup = {v: i for i, v in enumerate(vocab)}
            remap = np.empty(len(col.vocab), np.int32)
            for i, v in enumerate(col.vocab):
                if v not in lookup:
                    lookup[v] = len(vocab)
                    vocab.append(v)
                remap[i] = lookup[v]
            codes = np.where(col.codes >= 0, remap[np.maximum(col.codes, 0)], -1)
            cols[name] = DictColumn(codes.astype(np.int32), vocab)
            changed = True
        if not changed:
            return batch
        return FeatureBatch(batch.sft, cols, batch.fids, batch.valid)

    def _load_partition(self, name: str,
                        manifest: Optional[dict] = None,
                        ) -> Optional[CacheEntry]:
        batches = list(self.storage.scan_partitions([name],
                                                    manifest=manifest))
        if not batches:
            return None
        batch = FeatureBatch.concat(batches)
        n = len(batch)
        padded = batch.pad_to(_next_pow2(n))
        dev = None
        if self._flat and self._mesh_active():
            # mesh tier: no per-partition single-device segments — the
            # sharded superbatch is ONE NamedSharding upload of the host
            # concat, so uploading each partition here would double HBM.
            # The shared-vocab recode still runs so host/device code
            # spaces stay comparable across refreshes.
            padded = self._shared_vocab_recode(padded)
        elif self._flat:
            from geomesa_tpu.engine.device import to_device

            padded = self._shared_vocab_recode(padded)
            kw = {"coord_dtype": self.coord_dtype} if self.coord_dtype else {}
            # gt: waive GT09
            # (deliberate: the upload IS the guarded residency swap;
            # queries blocked here would otherwise read a half-registered
            # partition — double-buffer under the lock)
            dev = to_device(padded, **kw)
            self.upload_count += 1
            self.upload_rows += len(padded)
        return CacheEntry(
            files=self._partition_files(name, manifest),
            count=n,
            padded=len(padded),
            batch=padded,
            dev=dev,
        )

    @_locked
    def ensure(self, partitions: Optional[List[str]] = None,
               manifest: Optional[dict] = None) -> List[str]:
        """Make the named partitions (default: all) resident; returns the
        list actually (re)loaded. Already-resident, unchanged partitions are
        untouched — the double-buffer: a changed partition's new entry is
        fully built before the old one is dropped. `manifest` pins the
        whole ensure to one committed write version (the planner passes
        its plan-time snapshot so pruning and residency agree — without
        it, a concurrent batch-atomic write could be half-visible:
        reloaded files in old partitions, missing new partitions)."""
        mv = getattr(manifest, "version", None)
        if manifest is None or (mv is not None
                                and mv < self._applied_mversion):
            # a STALE plan snapshot (another query already applied a
            # newer commit) must not roll residency backward / thrash
            # re-uploads: take a fresh snapshot instead — it is at least
            # as new as anything applied
            manifest = self.storage.manifest_snapshot()
            mv = getattr(manifest, "version", None)
        if mv is not None:
            self._applied_mversion = max(self._applied_mversion, mv)
        names = partitions if partitions is not None else sorted(manifest)
        loaded = []
        for name in names:
            files = self._partition_files(name, manifest)
            cur = self._entries.get(name)
            if cur is not None and cur.files == files:
                continue
            entry = self._load_partition(name, manifest)
            changed = True
            if entry is None:
                # only a real removal changes residency — a partition that
                # can never load must not invalidate the superbatch on
                # every query
                changed = self._entries.pop(name, None) is not None
            else:
                self._entries[name] = entry  # atomic reference flip
            if changed:
                loaded.append(name)
        if loaded:
            self._super = None  # residency changed: superbatch stale
            self._version += 1
        return loaded

    @_locked
    def refresh(self) -> List[str]:
        """Re-sync with the storage manifest: load new/changed partitions,
        drop removed ones. Returns changed partition names."""
        manifest = self.storage.manifest_snapshot()
        dropped = [n for n in self._entries if n not in manifest]
        for n in dropped:
            del self._entries[n]
        if dropped:
            self._super = None
            self._version += 1
        return self.ensure(manifest=manifest) + dropped

    @_locked
    def invalidate(self, partition: Optional[str] = None) -> None:
        if partition is None:
            self._entries.clear()
        else:
            self._entries.pop(partition, None)
        self._super = None
        # a forced invalidation must actually free device state: the
        # delta-append path would otherwise keep the dropped rows alive
        self._mesh_prev = None
        self._version += 1

    @_locked
    def get(self, partition: str) -> Optional[CacheEntry]:
        return self._entries.get(partition)

    @_locked
    def superbatch_peek(self) -> Optional[SuperBatch]:
        """The CURRENT superbatch if one is built, else None — no
        residency work, no rebuild. The ring serve loop's per-window
        freshness gate (docs/SERVING.md "Persistent serve loop") must
        stay a lock acquire + identity compare, never an upload."""
        return self._super

    @_locked
    def superbatch(self) -> Optional[SuperBatch]:
        """The concatenated device view of every resident partition (None
        when nothing is resident). Built lazily and re-uploaded only when
        residency changes — the double-buffered snapshot idea at store
        granularity."""
        if self._super is not None:
            return self._super
        if not self._entries:
            return None
        import jax.numpy as jnp
        import numpy as np

        from geomesa_tpu.engine.device import to_device

        names = sorted(self._entries)
        entries = [self._entries[n] for n in names]
        batch = FeatureBatch.concat([e.batch for e in entries])
        pids_host = np.concatenate([
            np.full(e.padded, i, np.int32) for i, e in enumerate(entries)
        ])
        if self._mesh_active():
            return self._mesh_superbatch(names, entries, batch, pids_host)
        if self._flat and all(e.dev is not None for e in entries):
            # incremental path: DEVICE-side concat of the per-partition
            # segments — changed partitions were re-uploaded at load; the
            # unchanged ones never cross the host boundary again. The
            # shared-vocab recode (load time) makes dict-code segments
            # directly comparable; host `batch` concat re-encodes too but
            # the ORDER of first-appearance matches the grow-only vocab,
            # so host and device code spaces agree (asserted in tests).
            keys = entries[0].dev.keys()
            dev = {
                k: jnp.concatenate([e.dev[k] for e in entries])
                for k in keys
            }
        else:
            kw = {"coord_dtype": self.coord_dtype} if self.coord_dtype else {}
            # gt: waive GT09
            # (deliberate: full re-upload path of the superbatch rebuild;
            # the lock is what makes the swap atomic for concurrent
            # queries — see class docstring)
            dev = to_device(batch, **kw)
            self.upload_count += 1
            self.upload_rows += len(batch)
        self._super = SuperBatch(
            batch=batch,
            dev=dev,
            pids=jnp.asarray(pids_host),
            ids={n: i for i, n in enumerate(names)},
            version=self._version,
        )
        return self._super

    def _mesh_superbatch(self, names, entries, batch, pids_host):
        """Mesh-resident tier: the SERIAL layout (partitions in sorted
        order, each pow2-padded) plus trailing invalid padding to a
        multiple of the mesh size, uploaded ONCE via NamedSharding
        placement (`parallel.mesh.shard_device_batch` — no per-device
        device_put loops, the GT18 contract). Keeping the serial row
        layout is what makes sharded kNN indices bit-identical to the
        single-chip path; ownership is the row-range → shard map.

        Growth-phase cost (ROADMAP item 4 foundation): a residency
        GROWTH — new partitions appended at the end of the sorted
        layout, every already-resident entry byte-identical — uploads
        ONLY the delta tile (the new rows + fresh mesh padding) and
        reassembles the sharded arrays device-side from the previous
        superbatch's buffers, so `upload_rows` does not scale with
        resident size on append (regression-asserted in
        tests/test_device_cache.py). Everything else — a changed or
        removed partition, a name sorting into the middle, a mesh
        change — is layout-invalidating and takes the full host-concat
        re-upload (prior row ownership is stale there anyway). Old rows
        re-placed from device buffers are bit-identical to a fresh
        upload: the host copies are unchanged and the dict vocab is
        grow-only, so previously-uploaded codes never re-encode."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from geomesa_tpu.parallel.mesh import SHARD_AXIS

        d = int(self.mesh.devices.size)
        total = len(batch)
        padded_total = ((total + d - 1) // d) * d
        if padded_total > total:
            batch = batch.pad_to(padded_total)
            pids_host = np.concatenate([
                pids_host,
                # trailing pad rows carry the last pid; their validity
                # mask is False, so they are inert in every kernel
                np.full(padded_total - total, pids_host[-1], np.int32),
            ])
        # the GT09 waivers below are deliberate: the sharded upload IS
        # the guarded residency swap — the same device-work-under-the-
        # instance-lock contract the single-chip _load path carries
        from geomesa_tpu.engine.device import to_device

        kw = {"coord_dtype": self.coord_dtype} if self.coord_dtype else {}
        # flat stores carry only [N]-leading arrays, so ONE row-sharded
        # NamedSharding placement covers the whole batch — host rows go
        # straight to their owning chip, no single-device staging hop
        row = NamedSharding(self.mesh, P(SHARD_AXIS))
        prev = self._mesh_growth_prev(names)
        if prev is not None:
            # delta-append: host→device transfer covers ONLY the rows
            # past the previous concat (new partitions + the new mesh
            # padding); the old rows re-place from the previous device
            # buffers over ICI/device copies, never from the host
            old_concat = prev["concat_rows"]
            tail = batch.select(np.arange(old_concat, len(batch)))
            tail_dev = to_device(tail, **kw)  # gt: waive GT09
            self.upload_count += 1
            self.upload_rows += len(tail)
            dev = {
                # gt: waive GT09
                # (device-side reassembly under the residency lock —
                # same guarded-swap contract as the uploads above)
                k: jax.device_put(jnp.concatenate(
                    [prev["dev"][k][:old_concat], tail_dev[k]]), row)
                for k in tail_dev
            }
            pids = jax.device_put(jnp.concatenate(  # gt: waive GT09
                [prev["pids"][:old_concat],
                 jnp.asarray(pids_host[old_concat:])]), row)
        else:
            dev = to_device(batch, device=row, **kw)  # gt: waive GT09
            self.upload_count += 1
            self.upload_rows += len(batch)
            pids = jax.device_put(  # gt: waive GT09
                jnp.asarray(pids_host), row)
        shard_rows = padded_total // d
        owners: Dict[str, tuple] = {}
        off = 0
        for name, e in zip(names, entries):
            lo, hi = off, off + e.padded
            owners[name] = tuple(
                range(lo // shard_rows,
                      min((hi - 1) // shard_rows + 1, d)))
            off = hi
        self._super = SuperBatch(
            batch=batch,
            dev=dev,
            pids=pids,
            ids={n: i for i, n in enumerate(names)},
            version=self._version,
            mesh=self.mesh,
            shard_rows=shard_rows,
            owners=owners,
        )
        self._mesh_prev = {
            "mesh": self.mesh,
            "names": tuple(names),
            "meta": {n: (e.padded, tuple(e.files))
                     for n, e in zip(names, entries)},
            "concat_rows": total,
            "dev": dev,
            "pids": pids,
        }
        return self._super

    def _mesh_growth_prev(self, names) -> Optional[dict]:
        """The previous mesh layout IF the pending rebuild is a pure
        GROWTH against it: same mesh, the old name sequence is a strict
        prefix of the new sorted one (appends only — a name sorting
        into the middle shifts every later partition's rows), and every
        previously-resident entry is byte-identical (same padded length
        and file list). Anything else returns None → full re-upload."""
        prev = self._mesh_prev
        if prev is None or prev["mesh"] is not self.mesh:
            return None
        pn = prev["names"]
        if len(names) <= len(pn) or tuple(names[: len(pn)]) != pn:
            return None
        for name in pn:
            e = self._entries.get(name)
            meta = prev["meta"][name]
            if e is None or e.padded != meta[0] \
                    or tuple(e.files) != meta[1]:
                return None
        return prev

    @_locked
    def resident(self) -> List[str]:
        return sorted(self._entries)

    @_locked
    def stats(self) -> dict:
        return {
            "partitions": len(self._entries),
            "rows": sum(e.count for e in self._entries.values()),
            "padded_rows": sum(e.padded for e in self._entries.values()),
            "uploads": self.upload_count,
            "upload_rows": self.upload_rows,
            "layout_version": LAYOUT_VERSION,
        }

    # -- manifest persistence (restart determinism) ------------------------

    @property
    def manifest_path(self) -> str:
        return os.path.join(self.storage.root, MANIFEST)

    @_locked
    def save_manifest(self) -> None:
        from geomesa_tpu.parallel.distributed import is_coordinator

        if not is_coordinator():
            # multi-host: residency is globally consistent (every host
            # computes the same superbatch layout), so the manifests
            # would be byte-identical — one writer is the contract
            # anyway (GT27)
            return
        doc = {
            "layout_version": LAYOUT_VERSION,
            "coord_dtype": str(np.dtype(self.coord_dtype).name)
            if self.coord_dtype
            else None,
            "partitions": {
                name: {"files": e.files, "count": e.count, "padded": e.padded}
                for name, e in self._entries.items()
            },
        }
        tmp = self.manifest_path + ".tmp"
        # gt: waive GT09
        # (deliberate: manifest persistence under the lock keeps the
        # snapshot consistent with residency; the file swap is atomic)
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, self.manifest_path)

    @_locked
    def resume(self) -> Tuple[List[str], List[str]]:
        """Rebuild device state from the saved manifest: reload every
        partition it names whose files still match; report (restored,
        stale). Stale = layout drift or file-list drift — reloaded fresh
        via ensure() by the caller if wanted."""
        if not os.path.exists(self.manifest_path):
            return [], []
        # gt: waive GT09
        # (deliberate: restart-time rebuild — determinism of the restored
        # device state depends on the lock excluding queries)
        with open(self.manifest_path) as f:
            doc = json.load(f)
        restored, stale = [], []
        if doc.get("layout_version") != LAYOUT_VERSION:
            return [], sorted(doc.get("partitions", {}))
        snap = self.storage.manifest_snapshot()
        for name, meta in sorted(doc.get("partitions", {}).items()):
            if self._partition_files(name, snap) != meta["files"]:
                stale.append(name)
                continue
            entry = self._load_partition(name, snap)
            if entry is None:
                stale.append(name)
                continue
            assert entry.padded == meta["padded"], (
                f"non-deterministic rebuild for {name}: "
                f"{entry.padded} != {meta['padded']}"
            )
            self._entries[name] = entry
            restored.append(name)
        if restored:
            self._super = None  # residency changed: superbatch stale
            self._version += 1
        return restored, stale
