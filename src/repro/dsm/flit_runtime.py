"""The FliT-protocol durable commit for training state (paper Alg. 2 at
checkpoint granularity).

One *commit* of step ``s`` = the high-level operation; the state objects
(param shards, optimizer moments, data-pipeline state, RNG) are the shared
locations.  Following Alg. 2:

    for each object X:  flit_counter(X)++ ; LStore(X) ; RFlush(X) ;
                        flit_counter(X)--
    completeOp()  =  atomic manifest rename

Durable linearizability of the step history follows exactly as in the
paper's §B: a commit whose completeOp (manifest rename) finished survives
any single-worker crash; recovery always lands on SOME completed commit —
never a torn mixture of steps (tests: tests/test_dsm.py and the
process-kill suite in repro.scenarios).

Four schedules:

* ``sync``          — rflush every object serially, then completeOp
                      (simple, blocking; the baseline);
* ``async``         — overlap: one background flush thread per object runs
                      while step s+1 computes; the next commit joins them
                      before its completeOp;
* ``sharded``       — each object's pytree is split into ``n_shards``
                      byte-balanced leaf groups and written in PARALLEL
                      (one LStore/RFlush pipeline per shard on a thread
                      pool), then completeOp.  Blocking, but the flush
                      wall-time divides by the shard-level parallelism;
* ``sharded-async`` — the production default: sharded writes of step s are
                      double-buffered behind compute of step s+1; commit(s)
                      first joins + completeOps the PREVIOUS step's shards,
                      then launches step s's shard pipelines and returns.
                      The blocking cost is just the join of flushes that
                      already overlapped compute.

Retention: when ``retention=k`` is set, every completeOp is followed by
``pool.gc(keep=k)`` — old manifests and the shard versions only they
reference are deleted, bounding pool growth for long runs.

Fault injection: ``fault_hook(point, step)`` is called at the three
commit-window points ``pre_flush`` (state about to be flushed),
``mid_flush`` (first shard/object durable, manifest NOT yet written — a
kill here leaves a torn write) and ``post_completeOp`` (manifest rename
done).  The scenario runner (repro.scenarios) uses it to ``os._exit`` a
real worker process at each point and assert recovery lands on a completed
commit.  In ``*-async`` modes ``post_completeOp`` reports the PREVIOUS
step — the one whose manifest was just renamed.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from repro import obs
from repro.dsm import meshio
from repro.dsm.tiers import TierManager

COMMIT_MODES = ("sync", "async", "sharded", "sharded-async")

#: not a schedule itself: a committer built with ``mode=AUTO_MODE`` defers
#: to its PlacementPolicy at the first commit, which prices the flush
#: under the active topology and resolves to one of COMMIT_MODES
AUTO_MODE = "auto"

#: fault-injection points inside the commit window
KILL_POINTS = ("pre_flush", "mid_flush", "post_completeOp")


@dataclasses.dataclass
class CommitStats:
    step: int
    seq: int
    n_objects: int
    bytes_written: int
    wall_s: float
    mode: str
    n_shards: int = 1


def auto_shard_count(total_bytes: int, *,
                     min_shard_bytes: int = 1 << 20,
                     n_devices: Optional[int] = None) -> int:
    """THE default shard-count heuristic (single source of truth; the
    launcher re-uses it via train/step.py): one flush pipeline per local
    device, capped so no shard falls under ``min_shard_bytes`` — tiny
    states degrade gracefully to fewer pipelines.  ``n_devices`` pins the
    device term to a configured Mesh's size (a mesh-slice rank must size
    its pipelines from ITS sub-grid, not the whole process's devices)."""
    per_device = max(n_devices if n_devices is not None
                     else jax.local_device_count(), 1)
    by_bytes = max(total_bytes // min_shard_bytes, 1)
    return max(1, min(per_device, by_bytes))


class DurableCommitter:
    def __init__(self, tiers: TierManager, *, mode: str = "sync",
                 replicate_to: Optional[Any] = None,
                 n_shards: Optional[int] = None,
                 retention: Optional[int] = None,
                 fault_hook: Optional[Callable[[str, int], None]] = None,
                 placement: Optional[Any] = None,
                 mesh: Optional[Any] = None,
                 complete_fn: Optional[
                     Callable[[int, Dict[str, Any], Optional[dict]],
                              int]] = None):
        assert mode in COMMIT_MODES + (AUTO_MODE,), mode
        assert mode != AUTO_MODE or placement is not None, \
            "mode='auto' needs a PlacementPolicy to resolve the schedule"
        self.tiers = tiers
        self.mode = mode
        #: device-sharded commit: with a ``Mesh`` configured, the sharded
        #: schedules consume each device's buffer inside its own shard
        #: pipeline (tiers.rflush_sharded(device_local=True)) — no host
        #: gather of the full tree — and the shard count is derived from
        #: the mesh/sharding layout instead of a gathered-pytree balance.
        #: Shard FILES stay bit-identical to the host-gather path (the
        #: assignment is computed from the same per-leaf bytes), so
        #: recovery is format-compatible in both directions.
        self.mesh = mesh
        #: cost-driven placement (repro.dsm.placement).  When set, the
        #: shard count comes from ``placement.choose_shards`` (sized by
        #: the actual state bytes under the active topology) instead of
        #: the device-count heuristic, and ``mode="auto"`` resolves to
        #: the policy's schedule choice at the first commit.
        self.placement = placement
        self.replicate_to = replicate_to     # peer for RStore staging (a
        #                                      TierManager or any object
        #                                      with a .staging mapping, e.g.
        #                                      a cluster StagingProxy)
        self.n_shards = n_shards or None     # None = auto at first commit
        self.retention = retention
        self.fault_hook = fault_hook
        #: delegated completeOp: ``complete_fn(step, written, meta) -> seq``
        #: replaces the default single-writer ``pool.commit_manifest``.
        #: The cluster protocol (repro.dsm.cluster) uses this to turn a
        #: rank's flush into a rank-record + elected CLUSTER manifest
        #: commit; the flush machinery (schedules, shard pipelines, fault
        #: hooks) is reused unchanged.
        self.complete_fn = complete_fn
        #: (step, object names, meta) of the in-flight async commit.  meta
        #: is captured at LAUNCH so the manifest always describes the state
        #: that was actually flushed — a later commit's meta (e.g. a newer
        #: serving session table) must never pair with these objects.
        self._pending: Optional[Tuple[int, List[str], Optional[dict]]] = None

    def _hook(self, point: str, step: int):
        if self.fault_hook is not None:
            self.fault_hook(point, step)

    def _hbm_bytes(self) -> int:
        # emu.tree_nbytes is THE byte-counting used everywhere the
        # placement policy is fed sizes (commits, cluster ranks) — one
        # definition, so the same state never prices differently across
        # call sites
        from repro.dsm.emu import tree_nbytes
        return tree_nbytes(dict(self.tiers.hbm))

    def _resolve_shards(self) -> int:
        """Lazy auto shard count: sized from the actual HBM state volume
        at the first sharded flush — by the placement policy's cost model
        when one is configured, else the device-count heuristic.  With a
        Mesh, the policy prices from the REAL per-device byte loads
        (``meshio.per_device_nbytes``, metadata-only) and the heuristic's
        device term is the mesh's device count."""
        if self.n_shards is None:
            total = self._hbm_bytes()
            if self.placement is not None:
                device_bytes = (meshio.per_device_nbytes(
                    dict(self.tiers.hbm)) if self.mesh is not None else None)
                self.n_shards = self.placement.choose_shards(
                    total, device_bytes=device_bytes)
            else:
                self.n_shards = auto_shard_count(
                    total, n_devices=(meshio.mesh_device_count(self.mesh)
                                      if self.mesh is not None else None))
        return self.n_shards

    @property
    def _device_local(self) -> bool:
        """Sharded flushes consume device buffers directly iff a Mesh is
        configured — the host-gather path stays the default."""
        return self.mesh is not None

    def _resolve_mode(self) -> str:
        """``mode="auto"`` defers the schedule choice until the first
        commit, when the real state volume is known: the placement policy
        prices the flush under its topology and picks sync vs
        sharded-async (logged as a ``schedule`` decision)."""
        if self.mode == AUTO_MODE:
            self.mode = self.placement.choose_schedule(self._hbm_bytes())
        return self.mode

    def _complete_op(self, step: int, written: Dict[str, Any],
                     meta, t0, label: str) -> CommitStats:
        """completeOp = atomic manifest rename (or the delegated
        cluster-level completeOp), then retention GC."""
        if self.complete_fn is not None:
            seq = self.complete_fn(step, written, meta)
        else:
            seq = self.tiers.pool.commit_manifest(step, written, meta)
        # retention GC only in the single-committer configuration:
        # pool.gc deletes every version no kept manifest references, so
        # running it from one rank of a multi-writer pool would delete a
        # concurrent rank's flushed-but-not-yet-committed objects.  With a
        # delegated completeOp, retention is the cluster layer's job.
        if self.retention is not None and self.complete_fn is None:
            with obs.span("dsm.commit.gc") as sp:
                walked, deleted = self.tiers.pool.gc(keep=self.retention)
                sp.set(files=walked, deleted=deleted)
        st = CommitStats(step, seq, len(written),
                         sum(o.nbytes for o in written.values()),
                         time.perf_counter() - t0, label,
                         (self.n_shards or 1) if "sharded" in self.mode
                         else 1)
        self._hook("post_completeOp", step)
        return st

    # -- the Alg. 2 protocol over training state -----------------------------
    def update(self, objects: Dict[str, Any], step: Optional[int] = None):
        """Per-step LStore of the new state into HBM (always happens).
        If a peer is configured, also RStore-stage (cheap replication),
        tagged with the training step for recovery comparability."""
        for name, tree in objects.items():
            self.tiers.lstore(name, tree)
            if self.replicate_to is not None:
                self.tiers.rstore(name, self.replicate_to, tag=step)

    def commit(self, step: int, meta: Optional[dict] = None
               ) -> Optional[CommitStats]:
        """Durable commit of the current HBM state.  Blocking modes return
        the stats of THIS step; async modes return the stats of the
        PREVIOUS step whose flushes were just joined (None on the first
        call)."""
        t0 = time.perf_counter()
        mode = self._resolve_mode()
        with obs.span("dsm.commit", step=step, mode=mode):
            if mode == "async":
                return self._commit_async(step, meta, t0)
            if mode == "sharded-async":
                return self._commit_sharded_async(step, meta, t0)
            self._hook("pre_flush", step)
            written: Dict[str, Any] = {}
            first = True
            for name in self.tiers.hbm:
                if mode == "sharded":
                    written[name] = self.tiers.rflush_sharded(
                        name, self._resolve_shards(),
                        post_first_shard=self._mid_flush_probe(first, step),
                        device_local=self._device_local)
                else:
                    written[name] = self.tiers.rflush(name)
                    if first:
                        self._hook("mid_flush", step)
                first = False
            return self._complete_op(step, written, meta, t0, mode)

    def _mid_flush_probe(self, first: bool, step: int):
        """The mid-flush fault-injection callback — ONLY materialized when a
        fault hook is installed, because the tiers layer must synchronously
        wait on the first shard to fire it (which would serialize shard 0
        and block the async launch in normal operation)."""
        if not first or self.fault_hook is None:
            return None
        return lambda: self._hook("mid_flush", step)

    def _commit_async(self, step: int, meta, t0) -> Optional[CommitStats]:
        """Join the previous async flushes, completeOp them, then launch
        flushes of the CURRENT state in the background."""
        st = self._join_pending(t0, "async")
        self._hook("pre_flush", step)
        names = list(self.tiers.hbm)
        for i, name in enumerate(names):
            self.tiers.flush_async(name)
            if i == 0:
                # first object's durable write is in flight, manifest absent
                self._hook("mid_flush", step)
        self._pending = (step, names, meta)
        return st

    def _commit_sharded_async(self, step: int, meta, t0
                              ) -> Optional[CommitStats]:
        """Double-buffered sharded commit: join + completeOp step s-1's
        shard pipelines (they overlapped compute of step s), then launch
        step s's pipelines and return immediately."""
        st = self._join_pending(t0, "sharded-async")
        self._hook("pre_flush", step)
        names = list(self.tiers.hbm)
        first = True
        for name in names:
            self.tiers.flush_async_sharded(
                name, self._resolve_shards(),
                post_first_shard=self._mid_flush_probe(first, step),
                device_local=self._device_local)
            first = False
        self._pending = (step, names, meta)
        return st

    def _join_pending(self, t0, label: str) -> Optional[CommitStats]:
        if self._pending is None:
            return None
        prev_step, names, meta = self._pending
        self._pending = None        # cleared FIRST: a failed join must not
        #                             leave already-popped names re-joinable
        written: Dict[str, Any] = {}
        first_err: Optional[BaseException] = None
        with obs.span("dsm.commit.join", joined=prev_step):
            for n in names:
                try:
                    written[n] = self.tiers.flush_wait(n)
                except Exception as e:   # join the rest, raise the first
                    if first_err is None:
                        first_err = e
        if first_err is not None:
            raise first_err          # step simply not durable; no manifest
        return self._complete_op(prev_step, written, meta, t0, label)

    def drain(self, meta: Optional[dict] = None) -> Optional[CommitStats]:
        """Flush any pending async commit (planned shutdown — the paper's
        sanctioned GPF use case).  The manifest carries the meta captured
        when the pending commit LAUNCHED; ``meta`` is only a fallback for
        pre-capture callers."""
        if self._pending is not None:
            if self._pending[2] is None and meta is not None:
                self._pending = (*self._pending[:2], meta)
            return self._join_pending(time.perf_counter(), "drain")
        return None

    def abort_pending(self):
        """Crash path: discard the pending commit WITHOUT completing it.
        Outstanding writes are joined (so no stale write can land after the
        next incarnation starts) but no manifest is written — the step is
        simply not durable, exactly the partial-crash semantics."""
        self._pending = None
        self.tiers.abort_flushes()


def gpf_snapshot(committers, step: int, meta: Optional[dict] = None):
    """Global Persistent Flush (paper §3.2): drain EVERY worker's volatile
    tiers into the pool and commit a synchronized manifest.

    The paper deems GPF too blocking/fragile for the hot path but sanctions
    it for planned shutdown/snapshot; that is exactly this API's contract —
    the launcher calls it on SIGTERM or before elastic re-meshing.  Returns
    the per-worker commit stats."""
    stats = []
    for c in committers:
        c.drain(meta)
        stats.append(c.commit(step, meta))
        c.drain(meta)
    return stats
