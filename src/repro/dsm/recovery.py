"""Crash injection + recovery (partial-crash model, per the paper §3.1).

A worker crash loses its HBM and host-staging tiers; the pool and OTHER
workers are uninterrupted.  Recovery sources, best first:

1. **peer staging** — if a surviving peer holds an RStore-staged copy NEWER
   than the pool's manifest (CXL0 cache-to-cache propagation), adopt it.
   The peer may be an in-process ``TierManager`` or a cross-process
   staging view (``repro.dsm.cluster.FileStagingArea``) — anything with a
   ``.staging`` mapping of ``name -> (tag, host tree)``;
2. **pool manifest** — newest manifest whose every object CRC-validates;
   torn/corrupt shards trigger fallback to the previous manifest.  Works
   for plain AND sharded manifest entries: a sharded object validates only
   if EVERY shard validates, so a commit torn mid-shard-write is invisible.

``RecoveryManager.recover`` returns (state_objects, step, source).

Reads go through ``DSMPool.read_entry``, so recovery gets the streamed
format's mmap-backed zero-copy loads for free — and still reads legacy
``.npz`` objects written by older incarnations (the pool sniffs the
payload per object), so a fleet can recover across the format change.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

from repro import obs
from repro.dsm.pool import CorruptObjectError, DSMPool


class CrashError(Exception):
    """Raised by fault-injection hooks to simulate a worker loss."""


class ColdStartError(RuntimeError):
    """No recoverable state exists anywhere (empty pool, no peer staging).
    Subclasses RuntimeError for backward compatibility; resume paths catch
    THIS and never a broader class, so a real runtime failure during
    recovery cannot be mistaken for a cold start (which would shadow the
    pool with a fresh step -1 manifest)."""


class RecoveryManager:
    def __init__(self, pool: DSMPool):
        self.pool = pool

    def recover_from_pool(self, templates: Dict[str, Any], *,
                          exact: bool = True
                          ) -> Optional[Tuple[Dict[str, Any], int, int]]:
        """Newest fully-valid manifest -> (objects, step, seq).

        ``exact=True`` (default): the manifest's object set must equal the
        template set — the whole-state recovery of the training loop.
        ``exact=False``: the manifest may contain MORE objects than asked
        for (subset recovery) — e.g. a surviving cluster worker recovering
        only the victim rank's ``w<v>/...`` objects out of a cluster
        manifest that references every rank's."""
        for m in self.pool.manifests_desc():
            entries = m["objects"]
            if exact and set(entries) != set(templates):
                continue
            if not set(templates) <= set(entries):
                continue
            try:
                objs = {
                    name: self.pool.read_entry(name, entries[name],
                                               templates[name])
                    for name in templates}
            except (CorruptObjectError, KeyError, ValueError):
                # torn commit, or an object whose pytree structure no
                # longer matches the template (e.g. a pre-shrink manifest
                # read with post-repartition templates — tree_unflatten
                # raises ValueError): fall back to an older manifest
                continue
            return objs, m["step"], m["seq"]
        return None

    def recover_latest(self, template_for: Callable[[str, dict], Any]
                       ) -> Optional[Tuple[Dict[str, Any], dict]]:
        """Newest fully-CRC-valid manifest for a DYNAMIC object set.

        Unlike ``recover_from_pool`` (fixed training-state objects known up
        front), the object set here varies per manifest — e.g. one KV-cache
        object per live serving session.  ``template_for(name, entry)``
        returns the pytree prototype used to unflatten that object (the
        manifest's ``meta`` describes the set; the session store derives
        templates from it).  Returns ``(objects, manifest)`` for the newest
        manifest whose EVERY object validates, or None — torn commits fall
        back to older manifests exactly as in the fixed-set path."""
        for m in self.pool.manifests_desc():
            try:
                objs = {
                    name: self.pool.read_entry(
                        name, entry, template_for(name, entry))
                    for name, entry in m["objects"].items()}
            except (CorruptObjectError, KeyError, ValueError):
                continue            # torn commit (or template/structure
                #                     mismatch): fall back to older manifest
            return objs, m
        return None

    def recover(self, templates: Dict[str, Any],
                peers: Tuple[Any, ...] = (), *,
                exact: bool = True,
                ) -> Tuple[Dict[str, Any], int, str]:
        """Full recovery path: peer staging beats the pool if newer.

        ``templates``: pytree prototypes (for unflattening) per object.
        ``peers``: anything exposing a ``.staging`` mapping of
        ``name -> (tag, host tree)`` — an in-process TierManager, or a
        cross-process ``FileStagingArea.view(...)`` (repro.dsm.cluster)
        backed by a sibling worker's spill-file buffer.  Peer staging is
        only adopted if it covers ALL requested objects at one consistent
        version (else it could mix steps — not linearizable).
        ``exact=False`` allows subset recovery from the pool (see
        ``recover_from_pool``)."""
        with obs.span("dsm.recover", objects=len(templates)):
            pool_state = self.recover_from_pool(templates, exact=exact)
        best_peer: Optional[Dict[str, Any]] = None
        best_ver = -1
        for peer in peers:
            if not set(templates) <= set(peer.staging):
                continue
            staged = {n: peer.staging[n] for n in templates}
            vers = {v for v, _ in staged.values()}
            if len(vers) != 1:      # mixed-step staging: not consistent
                continue
            v = vers.pop()
            if v > best_ver:
                best_ver = v
                best_peer = {n: t for n, (_, t) in staged.items()}
        if pool_state is None and best_peer is None:
            raise ColdStartError("no recoverable state (cold start)")
        if best_peer is not None:
            # staged copies are tagged with the training step (see
            # DurableCommitter.update); newest wins against the manifest
            if pool_state is None or best_ver > pool_state[1]:
                return best_peer, best_ver, "peer-staging"
        objs, step, _ = pool_state
        return objs, step, "pool"
