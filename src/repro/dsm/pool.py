"""Persistent object pool — the ``M_k`` tier (owner memory) of the runtime.

On-disk layout (one directory per pool, usually on shared storage):

    pool/
      objects/<object>/<version>.cxl0      # streamed, self-validating frame
      objects/<object>.s<k>/<version>.cxl0 # shard k of a SHARDED write
      objects/<object>/<version>.npz       # LEGACY payload (+ .crc sidecar)
      manifest.json                        # CURRENT committed versions
      manifest.<n>.json                    # history (GC-bounded)

Write protocol (the MStore/RFlush realization):
  1. stream ``<version>.cxl0`` to a temp name — one pass, folding the
     CRC32 chunk-by-chunk as the bytes go out (``repro.dsm.stream``);
  2. fsync, then atomically rename into place.
The frame is self-validating (header CRC + folded payload CRC in the
footer), so no sidecar write/fsync is needed — half the fsyncs of the
legacy ``.npz`` + ``.crc`` pair, which the read path still accepts for
pools written before the streamed format existed.
A *commit* (``completeOp``) atomically renames a new ``manifest.json``
listing every object's version + CRC.  Readers validate CRCs; a torn or
bit-flipped shard fails validation and recovery falls back to the previous
manifest — the recovered state is always SOME completed commit (never torn),
which is exactly durable linearizability of the step history.  Reads are
mmap-backed and zero-copy: ``read_object`` returns ``np.frombuffer`` views
into private copy-on-write pages (``read_frame``), never an intermediate
deserialization buffer.

Multi-writer safety: a pool is a SHARED resource — several worker
processes (the cluster protocol, ``repro.dsm.cluster``) or a restarted
incarnation of the same committer may commit concurrently.
``commit_manifest`` therefore reserves its sequence number atomically: it
``O_EXCL``-creates ``manifest.<n>.json`` (re-scanning and retrying on
``FileExistsError``) and only then atomically renames the full document
over the reservation.  A reservation whose writer died before the rename
is an unparseable (empty) file that every reader skips; no completed
commit is ever overwritten.  Object names may be namespaced with ``/``
(the cluster protocol uses ``w<i>/<name>`` per worker); nested
directories are handled by ``max_version`` and ``gc``.

Sharded writes (the sharded/sharded-async commit schedules): a pytree's
leaves are partitioned into ``n_shards`` byte-balanced groups
(``partition_leaves``) and each group is written — usually in parallel, one
LStore/RFlush pipeline per shard — as an independent object
``<name>.s<k>``.  The manifest entry for a sharded object records every
shard's (name, version, crc) plus the leaf->shard ``assignment`` so readers
can reassemble the pytree (``read_entry``).  Durability is unchanged: no
shard is visible until the manifest rename, and a missing/corrupt shard
fails CRC validation of the WHOLE object, forcing fallback to the previous
manifest.  Manifest history is bounded by ``gc(keep=...)``, which retains
the newest ``keep`` manifests and deletes versions (plain or sharded) that
no retained manifest references.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import zipfile
import zlib
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax

from repro import obs
from repro.dsm import stream
from repro.dsm.stream import SpillArena  # noqa: F401  (re-export)


@dataclasses.dataclass
class PoolObject:
    name: str
    version: int
    crc: int
    nbytes: int


@dataclasses.dataclass
class ShardedObject:
    """One logical object written as ``len(shards)`` independent pool
    objects (``<name>.s<k>``).  ``assignment[k]`` lists the flattened-leaf
    indices stored in shard k."""
    name: str
    version: int
    nbytes: int
    n_leaves: int
    shards: List[PoolObject]
    assignment: List[List[int]]

    def to_entry(self) -> dict:
        return {
            "name": self.name, "version": self.version,
            "nbytes": self.nbytes, "n_leaves": self.n_leaves,
            "sharded": True,
            "shards": [dataclasses.asdict(s) for s in self.shards],
            "assignment": self.assignment,
        }


def manifest_entry(obj) -> dict:
    """Serialize a PoolObject / ShardedObject / ready-made dict for the
    manifest."""
    if isinstance(obj, ShardedObject):
        return obj.to_entry()
    if dataclasses.is_dataclass(obj):
        return dataclasses.asdict(obj)
    return dict(obj)


def shard_family(name: str) -> str:
    """The logical object a (possibly shard) name belongs to:
    ``params.s3`` -> ``params``, anything else unchanged.  gc's in-flight
    watermark is per FAMILY, because one committer may write an object
    plain while another manifest references it sharded (or with a
    different shard count) — they share one version counter."""
    base, dot, suffix = name.rpartition(".s")
    if dot and suffix.isdigit():
        return base
    return name


def partition_leaves(nbytes: List[int], n_shards: int) -> List[List[int]]:
    """Byte-balanced partition of leaf indices into ``<= n_shards`` groups
    (greedy: biggest leaf onto the lightest shard).  Never returns an empty
    shard — the shard count is clamped to the leaf count."""
    n_shards = max(1, min(n_shards, len(nbytes)))
    order = sorted(range(len(nbytes)), key=lambda i: -nbytes[i])
    loads = [0] * n_shards
    groups: List[List[int]] = [[] for _ in range(n_shards)]
    for i in order:
        k = min(range(n_shards), key=lambda j: loads[j])
        groups[k].append(i)
        loads[k] += nbytes[i]
    for g in groups:
        g.sort()
    return groups


def _flatten(tree) -> Tuple[List[np.ndarray], Any]:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return [np.asarray(l) for l in leaves], treedef


def _crc_of_arrays(arrays: List[np.ndarray]) -> int:
    crc = 0
    for a in arrays:
        crc = zlib.crc32(np.ascontiguousarray(a).tobytes(), crc)
    return crc


class CorruptObjectError(Exception):
    pass


#: dtypes numpy's npz round-trips natively; everything else (bfloat16,
#: float8 variants, ...) is stored as a raw byte view + sidecar dtype
_NATIVE_DTYPES = {
    "bool", "int8", "int16", "int32", "int64", "uint8", "uint16", "uint32",
    "uint64", "float16", "float32", "float64", "complex64", "complex128",
}


def encode_arrays(arrays: List[np.ndarray]
                  ) -> Tuple[List[np.ndarray], List[str], List[List[int]]]:
    """npz cannot round-trip ml_dtypes (bfloat16 etc.): return raw uint8
    views for non-native dtypes plus the (dtype, shape) sidecar data needed
    to reverse the view on read.  Shared by the pool write path and the
    cross-process staging area (``repro.dsm.cluster``)."""
    dtypes = [str(a.dtype) for a in arrays]
    raw = [np.ascontiguousarray(a).view(np.uint8)
           if d not in _NATIVE_DTYPES else a
           for a, d in zip(arrays, dtypes)]
    shapes = [list(a.shape) for a in arrays]
    return raw, dtypes, shapes


def decode_arrays(arrays: List[np.ndarray], dtypes: List[str],
                  shapes: List[List[int]]) -> List[np.ndarray]:
    """Reverse of ``encode_arrays``."""
    import ml_dtypes  # noqa: F401  (registers bfloat16 et al.)
    return [a if d in _NATIVE_DTYPES
            else a.view(np.dtype(d)).reshape(shape)
            for a, d, shape in zip(arrays, dtypes, shapes)]


class PendingWrite:
    """A streamed-but-not-yet-durable object write.  ``start_write``
    already pushed the whole frame (CRC folded during the stream) onto a
    temp file; ``finish`` pays the fsync and performs the atomic rename.
    Splitting the two lets the sharded flush pipelines stream shard k+1's
    bytes while shard k sits in its fsync — serialize/write and fsync
    overlap instead of queueing (``TierManager._shard_submit``)."""

    __slots__ = ("_pool", "name", "version", "crc", "nbytes",
                 "_file", "_tmp", "_dst")

    def __init__(self, pool: "DSMPool", name: str, version: int,
                 crc: int, nbytes: int, file, tmp: str, dst: str):
        self._pool = pool
        self.name = name
        self.version = version
        self.crc = crc
        self.nbytes = nbytes
        self._file = file
        self._tmp = tmp
        self._dst = dst

    def finish(self) -> PoolObject:
        """Make the write durable (fsync) and visible (atomic rename).
        MStore semantics: returns only once the object is on storage."""
        with obs.span("dsm.flush.fsync", name=self.name):
            f, self._file = self._file, None
            try:
                f.flush()
                os.fsync(f.fileno())
            finally:
                f.close()
            os.replace(self._tmp, self._dst)
            self._pool._finalize_write(self.name, self.version, self._dst)
        return PoolObject(self.name, self.version, self.crc, self.nbytes)

    def abort(self):
        """Drop an unfinished write (nothing became visible)."""
        if self._file is not None:
            try:
                self._file.close()
            finally:
                self._file = None
        try:
            os.unlink(self._tmp)
        except OSError:
            pass


class DSMPool:
    def __init__(self, path: str):
        self.path = path
        self.obj_dir = os.path.join(path, "objects")
        os.makedirs(self.obj_dir, exist_ok=True)
        self._manifest_seq = self._latest_manifest_seq()
        #: reusable spill-buffer arena of this pool's streamed writes
        #: (per-thread slots inside; sharded pipelines pass their
        #: TierManager's own arena via ``start_write(..., arena=)``)
        self._arena = stream.SpillArena()

    # -- low-level object IO -------------------------------------------------
    def _obj_path(self, name: str, version: int) -> str:
        d = os.path.join(self.obj_dir, name)
        os.makedirs(d, exist_ok=True)
        return os.path.join(d, f"{version:08d}")

    def payload_path(self, name: str, version: int) -> str:
        """The on-disk payload file of ``(name, version)`` — streamed
        ``.cxl0`` if present, else the legacy ``.npz`` (tests and the
        fault layer corrupt payloads through this)."""
        base = self._obj_path(name, version)
        if os.path.exists(base + stream.SUFFIX):
            return base + stream.SUFFIX
        if os.path.exists(base + ".npz"):
            return base + ".npz"
        return base + stream.SUFFIX

    def _mkstemp(self, base: str) -> Tuple[int, str]:
        try:
            return tempfile.mkstemp(dir=os.path.dirname(base))
        except FileNotFoundError:
            # a concurrent gc() rmdir'd the (momentarily empty) object dir
            # between our makedirs and mkstemp — recreate and retry once
            os.makedirs(os.path.dirname(base), exist_ok=True)
            return tempfile.mkstemp(dir=os.path.dirname(base))

    def start_write(self, name: str, version: int, tree,
                    arena: Optional[stream.SpillArena] = None
                    ) -> PendingWrite:
        """Stream one object version onto a temp file — the CPU half of a
        durable write (serialize + write + incremental CRC, single pass,
        no fsync).  Durability and visibility happen in the returned
        handle's ``finish()``."""
        with obs.span("dsm.flush.write", name=name) as sp:
            arrays, _ = _flatten(tree)
            base = self._obj_path(name, version)
            tmp_fd, tmp_name = self._mkstemp(base)
            f = os.fdopen(tmp_fd, "wb")
            try:
                crc, nbytes, _ = stream.write_frame(
                    f, arrays, arena or self._arena)
            except BaseException:
                f.close()
                try:
                    os.unlink(tmp_name)
                except OSError:
                    pass
                raise
            sp.set(bytes=nbytes)
        return PendingWrite(self, name, version, crc, nbytes, f,
                            tmp_name, base + stream.SUFFIX)

    def write_object(self, name: str, version: int, tree) -> PoolObject:
        """Durable write of one object version (MStore semantics: complete
        only once on physical storage).  One pass over the data: each
        leaf's buffer is streamed via memoryview in CHUNK-sized slices
        with the CRC32 folded as it goes — no ``np.savez`` zip walk, no
        second ``tobytes()`` CRC pass, no sidecar fsync."""
        pending = self.start_write(name, version, tree)
        try:
            return pending.finish()
        except BaseException:
            pending.abort()
            raise

    def _finalize_write(self, name: str, version: int, payload_path: str):
        """Hook: runs after a payload's atomic rename made it visible, in
        BOTH the one-shot and split-phase write paths.  The fault layer
        (``FaultyPool``) tears payloads here — keeping the injection on
        this hook rather than on ``write_object`` means pipelined shard
        writes stay corruptible and the fuzzer's oracle stays in sync."""

    def write_object_legacy(self, name: str, version: int,
                            tree) -> PoolObject:
        """The PR-6 write path: ``np.savez`` payload + JSON ``.crc``
        sidecar, two fsyncs, three passes over the data.  Kept (a) so
        backward-compat tests can fabricate old pools and (b) as the
        in-bench comparison baseline for the streamed fast path."""
        arrays, treedef = _flatten(tree)
        crc = _crc_of_arrays(arrays)
        base = self._obj_path(name, version)
        tmp_fd, tmp_name = self._mkstemp(base)
        os.close(tmp_fd)
        raw, dtypes, shapes = encode_arrays(arrays)
        with open(tmp_name, "wb") as f:
            np.savez(f, **{f"a{i}": a for i, a in enumerate(raw)})
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp_name, base + ".npz")
        meta = {"crc": crc, "treedef": str(treedef),
                "n": len(arrays), "dtypes": dtypes, "shapes": shapes}
        with open(base + ".crc.tmp", "w") as f:
            json.dump(meta, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(base + ".crc.tmp", base + ".crc")
        nbytes = sum(a.nbytes for a in arrays)
        self._finalize_write(name, version, base + ".npz")
        return PoolObject(name, version, crc, nbytes)

    def max_version(self, name: str) -> int:
        """Highest version present on disk for ``name`` INCLUDING its shard
        objects (``name.s<k>``) and torn/unreferenced files.  A fresh worker
        incarnation seeds its version counter above this so it can never
        overwrite a file an existing manifest still references.  Handles
        namespaced names (``w<i>/<name>``): the object dir and its shard
        sibling dirs live under the namespace directory."""
        return self.scan_versions(name)[0]

    def scan_versions(self, name: str) -> Tuple[int, int]:
        """``max_version`` and the number of directory entries its walk
        listed to find it."""
        best = listed = 0
        parent = os.path.dirname(os.path.join(self.obj_dir, name))
        base = os.path.basename(name)
        prefix = base + ".s"
        if not os.path.isdir(parent):
            return 0, 0
        entries = os.listdir(parent)
        listed += len(entries)
        for d in entries:
            if d != base and not (d.startswith(prefix)
                                  and d[len(prefix):].isdigit()):
                continue
            p = os.path.join(parent, d)
            if not os.path.isdir(p):
                continue
            files = os.listdir(p)
            listed += len(files)
            for fn in files:
                stem = fn.split(".")[0]
                if stem.isdigit():
                    best = max(best, int(stem))
        return best, listed

    def read_object(self, name: str, version: int, treedef_like,
                    expected_crc: Optional[int] = None) -> Any:
        """Read + CRC-validate one object version; raises CorruptObjectError
        on mismatch (recovery then falls back to an older manifest).
        ``expected_crc`` (the MANIFEST-recorded crc) additionally guards
        against the payload having been atomically replaced by a
        different write since the manifest committed.

        Streamed objects are mmap'd and returned as zero-copy
        ``np.frombuffer`` views (private copy-on-write pages); the CRC
        fold is one pass over the page cache.  Legacy ``.npz`` + sidecar
        pairs written by older pools take the original decode path."""
        base = self._obj_path(name, version)
        if os.path.exists(base + stream.SUFFIX):
            try:
                arrays, crc, _ = stream.read_frame(base + stream.SUFFIX)
            except (stream.FrameError, OSError) as e:
                raise CorruptObjectError(f"{name}@{version}: {e}") from e
            if expected_crc is not None and crc != expected_crc:
                raise CorruptObjectError(
                    f"{name}@{version}: content does not match the "
                    f"manifest (overwritten by a later write?)")
            _, treedef = jax.tree_util.tree_flatten(treedef_like)
            return jax.tree_util.tree_unflatten(treedef, arrays)
        try:
            with open(base + ".crc") as f:
                meta = json.load(f)
            with np.load(base + ".npz") as z:
                arrays = [z[f"a{i}"] for i in range(meta["n"])]
            if "dtypes" in meta:
                arrays = decode_arrays(arrays, meta["dtypes"], meta["shapes"])
        except (OSError, KeyError, ValueError, TypeError, EOFError,
                zipfile.BadZipFile, zlib.error) as e:
            raise CorruptObjectError(f"{name}@{version}: {e}") from e
        if _crc_of_arrays(arrays) != meta["crc"]:
            raise CorruptObjectError(f"{name}@{version}: CRC mismatch")
        if expected_crc is not None and meta["crc"] != expected_crc:
            raise CorruptObjectError(
                f"{name}@{version}: content does not match the manifest "
                f"(overwritten by a later write?)")
        _, treedef = jax.tree_util.tree_flatten(treedef_like)
        return jax.tree_util.tree_unflatten(treedef, arrays)

    # -- manifests (completeOp) ----------------------------------------------
    def _latest_manifest_seq(self) -> int:
        best = -1
        for fn in os.listdir(self.path):
            if fn.startswith("manifest.") and fn.endswith(".json"):
                mid = fn[len("manifest."):-len(".json")]
                if mid.isdigit():
                    best = max(best, int(mid))
        return best

    def _reserve_manifest_seq(self) -> Tuple[int, str]:
        """Atomically reserve the next manifest sequence number: O_EXCL
        create of ``manifest.<n>.json`` at n = newest-on-disk + 1, re-scan
        and retry on collision.  Two committers (concurrent workers, or a
        restarted incarnation racing a stale one) can therefore never pick
        the same n — the init-time cached ``_manifest_seq`` is only a hint
        and is NEVER trusted for the reservation."""
        while True:
            seq = max(self._latest_manifest_seq(), self._manifest_seq) + 1
            dst = os.path.join(self.path, f"manifest.{seq}.json")
            try:
                fd = os.open(dst, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
            except FileExistsError:
                self._manifest_seq = seq    # lost the race: scan past it
                continue
            os.close(fd)
            return seq, dst

    def commit_manifest(self, step: int, objects: Dict[str, Any],
                        meta: Optional[dict] = None) -> int:
        """Atomic commit: the step is durable iff the full manifest document
        replaced its reservation.  ``objects`` values may be PoolObject
        (plain), ShardedObject, or ready-made manifest-entry dicts.

        Multi-writer safe: the sequence number is reserved via O_EXCL
        create (see ``_reserve_manifest_seq``); the document is then
        written to a temp file, fsync'd, and atomically renamed OVER the
        reservation.  Readers either see the empty reservation (skipped as
        unparseable) or the complete document — a concurrent or restarted
        committer can never clobber a completed commit.

        The document is serialized and fsync'd ONCE: the convenience head
        pointer (``manifest.json``) is a hardlink to the same already-
        durable inode, atomically renamed into place — half the fsyncs of
        writing the document twice.  On filesystems without hardlinks the
        head falls back to a second write."""
        with obs.span("dsm.commit.manifest", objects=len(objects)) as sp:
            seq, dst = self._reserve_manifest_seq()
            self._manifest_seq = seq
            doc = {
                "seq": seq,
                "step": step,
                "objects": {name: manifest_entry(o)
                            for name, o in objects.items()},
                "meta": meta or {},
            }
            tmp = os.path.join(self.path, f".manifest.tmp.{seq}")
            with open(tmp, "w") as f:
                json.dump(doc, f)
                f.flush()
                sp.set(bytes=f.tell())
                os.fsync(f.fileno())
            # link the head's temp name to the fsync'd inode BEFORE the rename
            # consumes ``tmp`` — no second serialize, no second fsync
            head = os.path.join(self.path, "manifest.json")
            tmp2 = os.path.join(self.path, f".manifest.head.tmp.{seq}")
            try:
                os.link(tmp, tmp2)
            except OSError:
                tmp2 = None                 # no hardlinks here: write it twice
            os.replace(tmp, dst)
            # update the convenience head pointer last (also atomic; with
            # concurrent committers last-writer-wins — readers that need the
            # true newest manifest use manifests_desc())
            if tmp2 is None:
                tmp2 = os.path.join(self.path, f".manifest.head.tmp.{seq}")
                with open(tmp2, "w") as f:
                    json.dump(doc, f)
                    f.flush()
                    os.fsync(f.fileno())
            os.replace(tmp2, head)
        return seq

    def read_entry(self, name: str, entry: dict, treedef_like) -> Any:
        """Read + validate one manifest entry, plain or sharded, checking
        content against the manifest-recorded CRCs.  For a sharded entry
        every shard must validate — the shards are read in parallel,
        mirroring the write pipelines — and any torn or corrupt shard
        raises CorruptObjectError for the WHOLE object (recovery then falls
        back to an older manifest)."""
        with obs.span("dsm.recover.read", name=name):
            if not entry.get("sharded"):
                return self.read_object(name, entry["version"], treedef_like,
                                        expected_crc=entry.get("crc"))
            leaves: List[Any] = [None] * entry["n_leaves"]
            shards = list(zip(entry["shards"], entry["assignment"]))
            from concurrent.futures import ThreadPoolExecutor
            with ThreadPoolExecutor(max_workers=len(shards)) as ex:
                parts = list(ex.map(
                    lambda sa: self.read_object(
                        sa[0]["name"], sa[0]["version"], [0] * len(sa[1]),
                        expected_crc=sa[0].get("crc")),
                    shards))
            for (sh, idxs), part in zip(shards, parts):
                for i, a in zip(idxs, part):
                    leaves[i] = a
            if any(l is None for l in leaves):
                raise CorruptObjectError(
                    f"{name}@{entry['version']}: incomplete shard assignment")
            _, treedef = jax.tree_util.tree_flatten(treedef_like)
            return jax.tree_util.tree_unflatten(treedef, leaves)

    def manifests_desc(self) -> List[dict]:
        """All manifests, newest first — ordered by (step, seq), so logical
        time dominates.  With a single committer seq order IS step order;
        with concurrent committers a straggler may rename a manifest for an
        older step after a newer step's manifest landed (its seq is higher
        but its step is older), and recovery must still prefer the newest
        STEP.  Unparseable files (reservations whose writer died before the
        rename) are skipped."""
        out = []
        for fn in os.listdir(self.path):
            if fn.startswith("manifest.") and fn.endswith(".json"):
                mid = fn[len("manifest."):-len(".json")]
                if not mid.isdigit():
                    continue
                try:
                    with open(os.path.join(self.path, fn)) as f:
                        out.append(json.load(f))
                except (OSError, ValueError):
                    continue
        return sorted(out, key=lambda d: (-d["step"], -d["seq"]))

    def latest_manifest(self) -> Optional[dict]:
        ms = self.manifests_desc()
        return ms[0] if ms else None

    def gc(self, keep: int = 3) -> Tuple[int, int]:
        """Drop all but the newest ``keep`` manifests + unreferenced
        versions (the committer's retention policy calls this after every
        completeOp).  Handles sharded entries (every referenced shard stays
        live), namespaced objects (``w<i>/<name>`` — the walk is
        recursive), and skips files it cannot parse — e.g. tempfiles left
        by an incarnation that crashed mid-write — rather than aborting.

        Emptied object directories are removed: a long-lived pool that
        retires objects (e.g. serving's ``kv/<rid>`` with ``--retire-done``)
        must not accumulate thousands of stale ``objects/<name>/`` dirs
        forever.  A dir holding a tempfile of an in-flight write is not
        empty, so rmdir (which fails on non-empty dirs) never races a
        completed write; the one-in-a-million makedirs/mkstemp window is
        covered by write_object's retry.

        Dead manifest reservations (unparseable ``manifest.<n>.json`` whose
        writer crashed between reserve and rename) older than every kept
        manifest are deleted too — they can never become valid.

        Multi-writer tolerance: version counters are monotone per object
        (seeded above the on-disk max), so an unreferenced version NEWER
        than the newest kept reference of its object may be a concurrent
        writer's flushed-but-not-yet-committed file — gc never deletes
        those (once a later manifest references a higher version, a
        genuinely dead one falls behind the watermark and is collected).
        Versions of an object no kept manifest mentions at all are
        retired (e.g. a finished serving session's ``kv/<rid>``) and are
        deleted entirely, directory included.

        Returns the number of object files walked and of those deleted."""
        keep = max(1, keep)
        walked = deleted = 0
        ms = self.manifests_desc()
        keep_ms, drop_ms = ms[:keep], ms[keep:]
        live = set()
        #: family -> newest version any kept manifest references (the
        #: in-flight watermark; plain and sharded writes of one object
        #: share a version counter, so the family is the right key)
        watermark: Dict[str, int] = {}

        def _mark(name: str, version: int):
            fam = shard_family(name)
            watermark[fam] = max(watermark.get(fam, 0), version)

        for m in keep_ms:
            for n, o in m["objects"].items():
                if o.get("sharded"):
                    for s in o["shards"]:
                        live.add((s["name"], s["version"]))
                        _mark(s["name"], s["version"])
                else:
                    live.add((n, o["version"]))
                    _mark(n, o["version"])
        for m in drop_ms:
            try:
                os.unlink(os.path.join(self.path,
                                       f"manifest.{m['seq']}.json"))
            except OSError:
                pass
        if keep_ms:
            min_kept = min(m["seq"] for m in keep_ms)
            parsed = {m["seq"] for m in ms}
            for fn in os.listdir(self.path):
                if not (fn.startswith("manifest.") and fn.endswith(".json")):
                    continue
                mid = fn[len("manifest."):-len(".json")]
                if mid.isdigit() and int(mid) < min_kept \
                        and int(mid) not in parsed:
                    try:
                        os.unlink(os.path.join(self.path, fn))
                    except OSError:
                        pass
        for dirpath, dirnames, filenames in os.walk(self.obj_dir,
                                                    topdown=False):
            name = os.path.relpath(dirpath, self.obj_dir).replace(os.sep, "/")
            walked += len(filenames)
            for fn in filenames:
                stem = fn.split(".")[0]
                if not stem.isdigit():
                    continue        # tempfile from a crashed write
                v = int(stem)
                if (name, v) in live:
                    continue
                fam = shard_family(name)
                if fam in watermark and v > watermark[fam]:
                    continue    # newer than every kept reference of this
                    #             object: may be a concurrent writer's
                    #             in-flight commit
                try:
                    os.unlink(os.path.join(dirpath, fn))
                    deleted += 1
                except OSError:
                    pass
            if dirpath != self.obj_dir:
                try:
                    os.rmdir(dirpath)       # fails (harmlessly) if non-empty
                except OSError:
                    pass
        return walked, deleted
