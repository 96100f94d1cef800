"""Tier manager: HBM / host-staging / pool with CXL0 primitive semantics.

Per worker, per object:

* ``lstore(name, tree)``   — update the HBM tier (in-memory reference;
                             O(1), no copy — the training step already
                             produced the new arrays).  Marks dirty.
* ``rstore(name, peer)``   — stage a copy into a PEER worker's host buffer
                             (CXL0: store completing in the owner's cache).
                             Survives OUR crash; lost if the PEER crashes.
* ``rflush(name)``         — durable write of the current HBM value into the
                             pool.  Completes only when on storage (fsync).
* ``mstore(name, tree)``   — lstore + rflush fused (Prop. 1.8).

A background ``flush_async`` thread overlaps rflush I/O with compute; the
commit barrier (``DurableCommitter``) joins it before completeOp.

Sharded variants (``rflush_sharded`` / ``flush_async_sharded``) partition
the object's flattened leaves into byte-balanced shards and run one
LStore/RFlush pipeline per shard on a thread pool — the write path of the
sharded / sharded-async commit schedules.  When the pool's write path is
un-overridden the shard writes are SPLIT-PHASE (``DSMPool.start_write`` →
``PendingWrite.finish``): serialization/CRC of shard k+1 streams on the
flush pool while shard k's fsync runs on a dedicated one-thread fsync
lane — fsync releases the GIL, so the overlap is real even on one CPU.
``flush_wait`` joins either flavor; ``abort_flushes`` joins-and-discards
every outstanding write (used on crash recovery so a stale in-flight
write can never land AFTER a new incarnation started reusing version
numbers).
"""
from __future__ import annotations

import threading
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro import obs
from repro.dsm import meshio, stream
from repro.dsm.pool import (DSMPool, PoolObject, ShardedObject,
                            partition_leaves)


def _to_host(tree):
    """Device→host copy (the actual D2H of the staging tier).  A tree whose
    every leaf is already a host ``np.ndarray`` is returned as-is — the
    cluster spill path round-trips host arrays through here every step, and
    rebuilding an identical tree per call is pure overhead."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if all(type(l) is np.ndarray for l in leaves):
        return tree
    return jax.tree_util.tree_unflatten(treedef,
                                        [np.asarray(l) for l in leaves])


class TierManager:
    def __init__(self, pool: DSMPool, worker_id: int):
        self.pool = pool
        self.worker_id = worker_id
        self.hbm: Dict[str, Any] = {}               # C_i — device tier
        self.staging: Dict[str, Tuple[int, Any]] = {}   # peer-staged copies:
        #   name -> (version, host tree) staged INTO this worker by peers
        self.versions: Dict[str, int] = {}
        self.flit_counter: Dict[str, int] = {}
        self._flush_threads: Dict[str, threading.Thread] = {}
        self._flush_results: Dict[str, PoolObject] = {}
        self._flush_errors: Dict[str, BaseException] = {}
        #   name -> (version, n_leaves, assignment, shard futures)
        self._sharded_futures: Dict[
            str, Tuple[int, int, List[List[int]], List[Future]]] = {}
        self._executor: Optional[ThreadPoolExecutor] = None
        self._fsync_lane: Optional[ThreadPoolExecutor] = None
        self._arena = stream.SpillArena()   # reusable spill pack buffers
        self._lock = threading.Lock()
        #: D2H accounting (bytes).  ``d2h_gather_bytes`` counts whole-tree
        #: host gathers on the legacy flush paths; ``d2h_shard_bytes``
        #: counts the per-device buffer copies of device-local shard
        #: pipelines (``meshio.assemble_leaf``).  A device-sharded commit
        #: must leave ``d2h_gather_bytes`` untouched — the "no host gather
        #: of the full tree" contract, asserted in tests/test_mesh_commit.
        self.d2h_gather_bytes = 0
        self.d2h_shard_bytes = 0

    def _count_d2h(self, kind: str, nbytes: int):
        with self._lock:
            if kind == "gather":
                self.d2h_gather_bytes += int(nbytes)
            else:
                self.d2h_shard_bytes += int(nbytes)

    def _to_host_counted(self, tree):
        """``_to_host`` with D2H accounting: every leaf that is NOT already
        a host ndarray is gathered whole (the legacy full-tree D2H) and
        its bytes charged to ``d2h_gather_bytes``."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if all(type(l) is np.ndarray for l in leaves):
            return tree
        out = []
        for l in leaves:
            if type(l) is np.ndarray:
                out.append(l)
            else:
                a = np.asarray(l)
                self._count_d2h("gather", a.nbytes)
                out.append(a)
        return jax.tree_util.tree_unflatten(treedef, out)

    def _snapshot(self, name: str):
        """The host copy of ``name`` that a flush writes: the commit's
        device-to-host gather."""
        with obs.span("dsm.commit.snapshot", name=name) as sp:
            tree = self._to_host_counted(self.hbm[name])
            if sp:
                sp.set(bytes=sum(l.nbytes for l in
                                 jax.tree_util.tree_leaves(tree)))
        return tree

    def _get_executor(self, n_workers: int) -> ThreadPoolExecutor:
        """One lazily-created pool of flush pipelines, sized by the first
        sharded flush (the shard count is constant for a run)."""
        if self._executor is None:
            self._executor = ThreadPoolExecutor(
                max_workers=max(1, n_workers),
                thread_name_prefix=f"rflush-w{self.worker_id}")
        return self._executor

    def _get_fsync_lane(self) -> ThreadPoolExecutor:
        """One-thread executor that only runs ``PendingWrite.finish``
        (fsync + rename).  Serializing all fsyncs onto one lane lets the
        flush pool keep serializing/CRC-ing the NEXT shard while the
        current one flushes — fsync releases the GIL, so the pipeline
        genuinely overlaps even on a single CPU."""
        with self._lock:
            if self._fsync_lane is None:
                self._fsync_lane = ThreadPoolExecutor(
                    max_workers=1,
                    thread_name_prefix=f"fsync-w{self.worker_id}")
            return self._fsync_lane

    def _pool_write_is_stock(self) -> bool:
        """True iff the pool's write path is the stock ``DSMPool`` one —
        neither subclass-overridden nor instance-monkeypatched.  Fault
        harnesses and tests replace ``write_object`` wholesale (often
        with plain 3-positional-arg callables); the sharded pipeline must
        route through THAT override rather than the split-phase fast
        path, or the injection/assertion would be silently bypassed."""
        pool = self.pool
        return (type(pool).write_object is DSMPool.write_object
                and "write_object" not in pool.__dict__
                and type(pool).start_write is DSMPool.start_write
                and "start_write" not in pool.__dict__)

    # -- CXL0 primitive realizations ----------------------------------------
    def lstore(self, name: str, tree: Any):
        """Update the volatile HBM tier. Completes immediately.

        The first lstore of a name (fresh worker incarnation, or after a
        crash wiped the counters) seeds the version counter ABOVE the
        highest version already on disk: version numbers never repeat
        across incarnations, so a write can never overwrite a file a
        retained manifest still references."""
        self.hbm[name] = tree
        if name not in self.versions:
            with obs.span("dsm.lstore.seed", name=name) as sp:
                best, listed = self.pool.scan_versions(name)
                sp.set(entries=listed)
            self.versions[name] = best
        self.versions[name] += 1

    def rstore(self, name: str, peer: Any,
               tag: Optional[int] = None):
        """Stage our current value into a peer's host buffer.  On our crash
        the peer still holds it (newer than the pool) — CXL0's
        cache-to-cache propagation made useful (peer-cache recovery).
        ``tag`` (training step) makes staged copies comparable with pool
        manifests during recovery.  ``peer`` is anything exposing a
        ``.staging`` mapping: an in-process TierManager, or a
        cross-process ``StagingProxy`` (repro.dsm.cluster) that writes
        through to a sibling worker's spill-file buffer.

        The D2H copy is DEFERRED when the peer's buffer declares
        ``materializes_leaves`` (the spill-file buffer copies each leaf
        to host as it streams the frame): emulator-priced paths already
        charge the transfer from leaf ``nbytes`` at call time, so a
        placement policy can reject the spill without this method ever
        having paid the copy it would have skipped.  In-process dict
        peers still get an eager host snapshot — their staging entries
        are read back directly (recovery oracle, rload)."""
        tree = self.hbm[name]
        if not getattr(peer.staging, "materializes_leaves", False):
            tree = self._to_host_counted(tree)
        peer.staging[name] = (self.versions.get(name, 0) if tag is None
                              else tag, tree)

    def ldiscard(self, name: str):
        """Drop an object from the volatile HBM tier (slot freed — e.g. a
        finished serving session's KV cache).  The version counter is KEPT:
        if the name is ever lstored again the counter keeps rising, so a
        late write can never collide with a pool file an older manifest
        still references.  No-op if absent."""
        self.hbm.pop(name, None)

    def rload(self, name: str) -> Optional[Any]:
        """Read back a value staged INTO this worker's host buffer by a
        peer's rstore (the staging-tier restore path).  Returns the host
        tree or None."""
        staged = self.staging.get(name)
        return None if staged is None else staged[1]

    def rflush(self, name: str) -> PoolObject:
        """Durable write; returns once the object is on storage."""
        self.flit_counter[name] = self.flit_counter.get(name, 0) + 1
        try:
            obj = self.pool.write_object(
                name, self.versions.get(name, 0), self._snapshot(name))
        finally:
            self.flit_counter[name] -= 1
        return obj

    def mstore(self, name: str, tree: Any) -> PoolObject:
        self.lstore(name, tree)
        return self.rflush(name)

    # -- sharded flush (parallel per-shard RFlush pipelines) -----------------
    def _shard_submit(self, name: str, n_shards: int,
                      post_first_shard: Optional[Callable] = None,
                      device_local: bool = False
                      ) -> Tuple[int, int, List[List[int]], List[Future]]:
        """Snapshot the object NOW, partition its leaves into byte-balanced
        shards, and submit one write per shard to the flush pool.  If
        ``post_first_shard`` is given it runs after the FIRST shard is
        durable and before the rest are joined — the mid-flush
        fault-injection point of the scenario runner.

        ``device_local=True`` (mesh-native commit): the assignment comes
        from leaf METADATA (``meshio.leaf_nbytes`` — identical bytes to
        the gathered path, so the assignment and hence every shard file
        is bit-identical), and each shard is submitted as a THUNK that
        materializes only its own leaves from their per-device buffers
        inside that shard's pipeline (``meshio.assemble_leaf``).  The
        full tree is never gathered on host — ``d2h_gather_bytes`` stays
        untouched; per-buffer copies land in ``d2h_shard_bytes``.  jax
        arrays are immutable, so snapshotting by reference here and
        copying inside the pipeline observes the same value the caller
        committed."""
        version = self.versions.get(name, 0)
        if device_local:
            tree_leaves = jax.tree_util.tree_leaves(self.hbm[name])
            sizes = [meshio.leaf_nbytes(l) for l in tree_leaves]
            assignment = partition_leaves(sizes, n_shards)
            n_leaves = len(tree_leaves)

            def _shard_thunk(idxs):
                def thunk():
                    return [meshio.assemble_leaf(
                        tree_leaves[i],
                        lambda nb: self._count_d2h("shard", nb))
                        for i in idxs]
                return thunk

            shards = [_shard_thunk(tuple(idxs)) for idxs in assignment]
        else:
            leaves = [np.asarray(l) for l in jax.tree_util.tree_leaves(
                self._snapshot(name))]
            assignment = partition_leaves(
                [a.nbytes for a in leaves], n_shards)
            n_leaves = len(leaves)
            shards = [[leaves[i] for i in idxs] for idxs in assignment]
        ex = self._get_executor(len(assignment))
        pipelined = self._pool_write_is_stock()
        futs = []
        try:
            for k, shard in enumerate(shards):
                if pipelined:
                    futs.append(self._submit_split_phase(
                        ex, f"{name}.s{k}", version, shard))
                else:
                    futs.append(ex.submit(obs.carry(self._write_shard),
                                          f"{name}.s{k}", version, shard))
                if k == 0 and post_first_shard is not None:
                    futs[0].result()
                    post_first_shard()
        except BaseException:
            # the mid-flush hook (fault injection) may raise between
            # submissions: already-submitted shard writes must fully land
            # (or fail) before the caller unwinds, else an untracked stale
            # write could race a later incarnation's version reuse
            for f in futs:
                try:
                    f.result()
                except Exception:
                    pass
            raise
        return version, n_leaves, assignment, futs

    def _write_shard(self, name: str, version: int, shard) -> PoolObject:
        """Write one shard via the pool's (possibly overridden)
        ``write_object``; a callable shard is a device-local materializer
        thunk and is resolved HERE, on the shard's own pipeline thread."""
        arrs = shard() if callable(shard) else shard
        return self.pool.write_object(name, version, arrs)

    def _submit_split_phase(self, ex: ThreadPoolExecutor, name: str,
                            version: int, leaves) -> Future:
        """Submit one shard write as a two-stage pipeline: the flush pool
        thread serializes + CRCs the frame (``start_write``, no fsync),
        then hands the pending write to the one-thread fsync lane for
        ``finish`` (fsync + atomic rename).  The returned future resolves
        only after the rename — same durability point as a monolithic
        ``write_object`` — but while shard k sits in its fsync, the flush
        pool is already streaming shard k+1's bytes.  ``leaves`` may be a
        device-local materializer thunk; it runs on the flush-pool thread
        so the D2H copies overlap across shard pipelines."""
        out: Future = Future()

        def serialize():
            try:
                arrs = leaves() if callable(leaves) else leaves
                pending = self.pool.start_write(name, version, arrs,
                                                arena=self._arena)
            except BaseException as e:
                out.set_exception(e)
                return
            def finish():
                try:
                    out.set_result(pending.finish())
                except BaseException as e:
                    try:
                        pending.abort()
                    except Exception:
                        pass
                    out.set_exception(e)
            try:
                self._get_fsync_lane().submit(obs.carry(finish))
            except BaseException as e:     # lane torn down mid-shutdown
                pending.abort()
                out.set_exception(e)

        ex.submit(obs.carry(serialize))
        return out

    def _shard_join(self, name: str, version: int, n_leaves: int,
                    assignment: List[List[int]],
                    futs: List[Future]) -> ShardedObject:
        """Join EVERY shard future (a failed shard must not leave later
        shards' writes in flight), then surface the first failure."""
        shards, first_err = [], None
        for f in futs:
            try:
                shards.append(f.result())
            except BaseException as e:
                if first_err is None:
                    first_err = e
        if first_err is not None:
            raise first_err
        return ShardedObject(name, version,
                             sum(s.nbytes for s in shards),
                             n_leaves, shards, assignment)

    def rflush_sharded(self, name: str, n_shards: int,
                       post_first_shard: Optional[Callable] = None,
                       device_local: bool = False) -> ShardedObject:
        """Blocking sharded durable write: all shards written in parallel,
        returns once every shard is on storage.  ``device_local=True``
        consumes per-device buffers inside each shard pipeline instead of
        gathering the tree first (see ``_shard_submit``)."""
        self.flit_counter[name] = self.flit_counter.get(name, 0) + 1
        try:
            return self._shard_join(
                name, *self._shard_submit(name, n_shards, post_first_shard,
                                          device_local=device_local))
        finally:
            self.flit_counter[name] -= 1

    def flush_async_sharded(self, name: str, n_shards: int,
                            post_first_shard: Optional[Callable] = None,
                            device_local: bool = False):
        """Start a sharded durable write in the background (double-buffered
        commit path); join via flush_wait.  The FliT counter stays raised
        until the join, so a concurrent joiner knows the pool copy may be
        stale."""
        self.flit_counter[name] = self.flit_counter.get(name, 0) + 1
        try:
            self._sharded_futures[name] = self._shard_submit(
                name, n_shards, post_first_shard,
                device_local=device_local)
        except BaseException:
            self.flit_counter[name] -= 1     # nothing tracked -> no join
            raise

    # -- async flush (compute/IO overlap) ------------------------------------
    def flush_async(self, name: str):
        """Start a durable write in the background; join via flush_wait.
        The FliT counter stays raised until the write completes, so any
        concurrent joiner knows the pool copy may be stale."""
        self.flit_counter[name] = self.flit_counter.get(name, 0) + 1
        version = self.versions.get(name, 0)
        host_copy = self._snapshot(name)                   # snapshot NOW

        def work():
            # a failed write must surface at the join (flush_wait) AND the
            # FliT counter must come back down either way — a leaked raised
            # counter would make every later joiner think the pool copy is
            # permanently stale
            try:
                obj = self.pool.write_object(name, version, host_copy)
            except BaseException as e:
                with self._lock:
                    self._flush_errors[name] = e
            else:
                with self._lock:
                    self._flush_results[name] = obj
            finally:
                with self._lock:
                    self.flit_counter[name] -= 1

        t = threading.Thread(target=obs.carry(work), daemon=True)
        self._flush_threads[name] = t
        t.start()

    def flush_wait(self, name: str):
        """Join one outstanding async flush (threaded or sharded); returns
        the PoolObject / ShardedObject for the manifest.  A write that
        failed in the background re-raises its exception HERE — the commit
        simply is not durable (no manifest); the caller decides whether to
        retry or abort."""
        pending = self._sharded_futures.pop(name, None)
        if pending is not None:
            try:
                return self._shard_join(name, *pending)
            finally:
                self.flit_counter[name] -= 1
        t = self._flush_threads.pop(name, None)
        if t is not None:
            t.join()
        with self._lock:
            err = self._flush_errors.pop(name, None)
            if err is not None:
                raise err
            return self._flush_results.pop(name)

    def abort_flushes(self):
        """Join-and-discard every outstanding async write.  Called on crash
        recovery: a stale write must fully land (or fail) BEFORE the next
        incarnation reuses version numbers, else an old flush could
        overwrite a new one's file after its manifest committed."""
        outstanding = (sum(len(p[3]) for p in self._sharded_futures.values())
                       + len(self._flush_threads))
        with obs.span("dsm.crash.join", writes=outstanding):
            for name, (_, _, _, futs) in list(
                    self._sharded_futures.items()):
                for f in futs:
                    try:
                        f.result()
                    except Exception:
                        pass
                self.flit_counter[name] -= 1
            self._sharded_futures.clear()
            for name, t in list(self._flush_threads.items()):
                t.join()        # work()'s finally lowered the counter,
            #                     whether the write landed or failed
            self._flush_threads.clear()
        with self._lock:
            self._flush_results.clear()
            self._flush_errors.clear()

    def close(self):
        """Release the flush thread pool and fsync lane (idempotent;
        lazily recreated if another sharded flush happens)."""
        if self._executor is not None:
            self._executor.shutdown(wait=False)
            self._executor = None
        with self._lock:
            lane, self._fsync_lane = self._fsync_lane, None
        if lane is not None:
            lane.shutdown(wait=False)

    # -- crash ----------------------------------------------------------------
    def crash(self):
        """f_i: all volatile tiers of this worker vanish."""
        self.abort_flushes()
        self.close()
        self.hbm.clear()
        self.staging.clear()
        self.versions.clear()
        self.flit_counter.clear()
        self._flush_threads.clear()
        self._flush_results.clear()
        self._flush_errors.clear()
