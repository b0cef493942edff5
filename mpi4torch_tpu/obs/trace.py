"""Chokepoint comm tracing: the runtime half of the observability layer.

A :class:`CommTracer` installed via :func:`trace` (lexical) or
``config.set_comm_tracer`` (process-wide) observes every Mode B
communication operation at the two chokepoints all subsystems already
funnel through — ``World.exchange`` and the p2p mailboxes
(runtime.py) — so fused buckets, compressed wires, overlap pipelines,
reshard plans, and serving decode traffic are traced with ZERO
per-subsystem hooks (the PR 7 fault-injection discipline, applied to
observation instead of perturbation).

Off path: one attribute read per chokepoint (``config.comm_tracer()``
returning None), the same zero-overhead contract as the fault plan and
the integrity guards; tests/test_obs.py holds that the obs-off Mode A
lowering is bit-identical to an obs-less build.

Mode A coverage: :func:`spmd_collective_event` is a trace-time hook
(the ``spmd_finite_value`` precedent) at the SPMD collective entries —
with tracing off (or ``mode_a=False``) it returns its argument
untouched, adding zero ops; with ``mode_a=True`` it attaches a host
``jax.debug.callback`` that emits one step-level event per executed
collective entry.  The flag rides ``config.thresholds_fingerprint``,
so toggling retraces instead of silently reusing the old lowering.

The tracer also owns the **flight recorder** state: a bounded per-rank
ring of recent events, snapshotted into a rank-attributed postmortem
the moment a chokepoint raises ``RankFailedError`` / ``DeadlockError``
/ ``IntegrityError`` (see :mod:`.flight` for the report format).
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

from .. import config as _config
from .events import CommEvent, annotate_signature, payload_nbytes

__all__ = [
    "CommTracer",
    "trace",
    "current_tracer",
    "spmd_collective_event",
    "push_label",
    "current_label",
]

# Errors that trigger a flight-recorder postmortem snapshot.  Resolved
# lazily (runtime imports config; importing runtime here at module load
# would be circular through the package __init__).
_FAILURE_TYPES = None


def _failure_types():
    global _FAILURE_TYPES
    if _FAILURE_TYPES is None:
        from ..elastic.membership import ConsensusError
        from ..resilience.health import SlowRankError
        from ..runtime import (DeadlockError, IntegrityError,
                               RankFailedError)
        # ConsensusError rides the same reaper entry point every other
        # attributed failure does (run_ranks routes rank failures to
        # note_rank_failure) — a failed resize gets its flight-recorder
        # postmortem with zero new hooks.  SlowRankError (ISSUE 15)
        # joins the set the same way: a gray-failure escalation raised
        # inside a rank body snapshots a postmortem through the reaper,
        # and a driver-side escalation calls note_gray_failure directly.
        _FAILURE_TYPES = (RankFailedError, DeadlockError, IntegrityError,
                          ConsensusError, SlowRankError)
    return _FAILURE_TYPES


# ------------------------------------------------------- label context

# Thread-local label stack the bucket/step scopes push (see
# utils/profiling.bucket_scope): gives Mode B events their
# bucket/codec/phase label even though jax.named_scope is invisible to
# the eager chokepoints.  Pushed only while a tracer is installed, so
# the scopes stay free when observability is off.
_labels = threading.local()


def push_label(label: str):
    """Context manager pushing ``label`` onto this thread's scope-label
    stack (no-op object when no tracer is installed)."""
    return _LabelCtx(label)


class _LabelCtx:
    __slots__ = ("label", "_pushed")

    def __init__(self, label: str):
        self.label = label
        self._pushed = False

    def __enter__(self):
        if _config.comm_tracer() is not None:
            stack = getattr(_labels, "stack", None)
            if stack is None:
                stack = _labels.stack = []
            stack.append(self.label)
            self._pushed = True
        return self

    def __exit__(self, *exc):
        if self._pushed:
            _labels.stack.pop()
        return False


def current_label() -> Optional[str]:
    """Innermost bucket/step label pushed on this thread, or None."""
    stack = getattr(_labels, "stack", None)
    return stack[-1] if stack else None


class _Meter:
    """Per-operation measurement state handed through the chokepoint:
    the runtime's retry loops add into ``retries`` (the per-waiter
    semantics of ``World.retry_events``), commit computes the wall
    duration."""

    __slots__ = ("tracer", "world_ord", "world_size", "rank", "channel",
                 "signature", "payload_bytes", "peer", "tag", "t0",
                 "retries", "bucket", "wait_s")

    def __init__(self, tracer, world_ord, world_size, rank, channel,
                 signature, payload_bytes, peer, tag):
        self.tracer = tracer
        self.world_ord = world_ord
        self.world_size = world_size
        self.rank = rank
        self.channel = channel
        self.signature = signature
        self.payload_bytes = payload_bytes
        self.peer = peer
        self.tag = tag
        self.bucket = current_label()
        self.retries = 0
        self.wait_s = 0.0
        self.t0 = time.perf_counter()

    def add_retries(self, n: int) -> None:
        self.retries += n

    def add_wait(self, seconds: float) -> None:
        """Barrier-blocked time the runtime reports (both rendezvous
        barriers of an exchange add in) — the gray-failure detector's
        local-vs-wait split (resilience.health)."""
        self.wait_s += seconds


class CommTracer:
    """Thread-safe collector of :class:`CommEvent` records.

    * ``events`` — the global program-order list (bounded by
      ``max_events``; drops-oldest beyond it, counted in ``dropped`` —
      silent truncation would falsify the reconcile census, so the
      reconciler refuses a trace that dropped events).
    * per-``(world, rank)`` ring buffers of the last ``ring`` events —
      the flight recorder's tail state.
    * ``postmortems`` — rank-attributed failure snapshots (first
      failure per world wins; later observers of the same tear
      increment its ``observers`` count instead of re-dumping).
    * ``mode_a`` — whether :func:`spmd_collective_event` instruments
      Mode A lowerings (priced: one host callback per collective
      entry; part of the jit fingerprint).
    """

    def __init__(self, ring: int = 64, max_events: int = 200_000,
                 mode_a: bool = False):
        self.ring = int(ring)
        self.max_events = int(max_events)
        self.mode_a = bool(mode_a)
        # Bounded deque: O(1) drop-oldest past the cap (a list's
        # del [0] would shift the whole buffer under the lock on every
        # event of a long-running traced fleet).
        self.events: collections.deque = collections.deque(
            maxlen=self.max_events)
        self.dropped = 0
        self.postmortems: List[dict] = []
        self._rings: Dict[tuple, collections.deque] = {}
        self._worlds: Dict[int, int] = {}     # id(world) -> ordinal
        self._failed_worlds: Dict[int, int] = {}   # ordinal -> pm index
        self._world_ctr = itertools.count()
        self._seq = itertools.count()
        self._lock = threading.Lock()

    # ----------------------------------------------------------- plumbing

    def _world_ord(self, world) -> int:
        wid = id(world)
        with self._lock:
            got = self._worlds.get(wid)
            if got is None:
                got = self._worlds[wid] = next(self._world_ctr)
            return got

    def begin(self, world, rank: int, channel: str, signature,
              payload=None, peer: Optional[int] = None,
              tag: Optional[int] = None) -> _Meter:
        return _Meter(self, self._world_ord(world), world.size, rank,
                      channel, signature,
                      payload_nbytes(payload) if payload is not None
                      else 0, peer, tag)

    def commit(self, meter: _Meter, result_payload=None,
               error: Optional[BaseException] = None) -> None:
        """Finalize one operation into an event.  ``result_payload``
        (p2p receives) contributes the received bytes; ``error`` marks
        the status and — for the attributed failure classes — triggers
        the flight-recorder postmortem."""
        dur = time.perf_counter() - meter.t0
        if result_payload is not None:
            meter.payload_bytes += payload_nbytes(result_payload)
        ann = annotate_signature(meter.signature)
        ev = CommEvent(
            seq=next(self._seq), rank=meter.rank, world=meter.world_ord,
            world_size=meter.world_size, channel=meter.channel,
            op=ann["op"], signature=(meter.signature if isinstance(
                meter.signature, tuple) else (meter.signature,)),
            payload_bytes=meter.payload_bytes, duration_s=dur,
            wait_s=meter.wait_s, t_start=meter.t0, retries=meter.retries,
            status="ok" if error is None else type(error).__name__,
            family=ann.get("family"), bookkeeping=ann["bookkeeping"],
            unmodeled=ann.get("unmodeled", False),
            algorithm=ann.get("algorithm"), codec=ann.get("codec"),
            bucket=meter.bucket, group_size=ann.get("group_size"),
            shape=ann.get("shape"), dtype=ann.get("dtype"),
            peer=meter.peer, tag=meter.tag)
        self._append(ev)
        if error is not None and isinstance(error, _failure_types()):
            self._note_failure(ev, error)

    def _append(self, ev: CommEvent) -> None:
        with self._lock:
            if len(self.events) == self.max_events:
                self.dropped += 1   # deque maxlen drops the oldest
            self.events.append(ev)
            key = (ev.world, ev.rank)
            ring = self._rings.get(key)
            if ring is None:
                ring = self._rings[key] = collections.deque(
                    maxlen=self.ring)
            ring.append(ev)
        from . import metrics as _metrics
        _metrics.inc("obs_events_total",
                     help="CommEvents recorded by the comm tracer")

    def _note_failure(self, ev: CommEvent, error: BaseException) -> None:
        from .flight import build_postmortem
        with self._lock:
            idx = self._failed_worlds.get(ev.world)
            if idx is not None:
                # A later observer of an already-snapshotted tear:
                # refresh ITS tail (it has just committed its own view
                # of the torn collective — the first snapshot raced
                # peers still blocked in the barrier) and count it.
                pm = self.postmortems[idx]
                pm["observers"] += 1
                pm["observer_ranks"] = sorted(set(
                    pm["observer_ranks"] + [ev.rank]))
                ring = self._rings.get((ev.world, ev.rank))
                if ring:
                    pm["tails"][ev.rank] = [e.to_dict() for e in ring]
                return
            pm = build_postmortem(self, ev, error)
            self._failed_worlds[ev.world] = len(self.postmortems)
            self.postmortems.append(pm)
        from . import metrics as _metrics
        _metrics.inc("obs_postmortems_total",
                     help="flight-recorder postmortems captured")

    def note_rank_failure(self, world, rank: int,
                          error: BaseException) -> None:
        """Postmortem entry point for failures raised OUTSIDE the
        chokepoints (integrity guards verify the decoded list after the
        rendezvous returns; ``run_ranks``' reaper routes every rank
        failure here).  Only the attributed failure classes snapshot;
        the per-world dedup in ``_note_failure`` means a failure already
        captured at a chokepoint just gains an observer."""
        if not isinstance(error, _failure_types()):
            return
        ev = CommEvent(
            seq=next(self._seq), rank=rank,
            world=self._world_ord(world), world_size=world.size,
            channel="exchange", op=f"({type(error).__name__})",
            status=type(error).__name__)
        self._note_failure(ev, error)

    def note_gray_failure(self, world_ord: int, world_size: int,
                          rank: int, error: BaseException) -> None:
        """Postmortem entry point for DRIVER-side gray-failure
        escalations (mpi4torch_tpu.resilience.health): the detector
        runs between phases, outside any rank body, so there is no
        world object and no reaper — it names the traced world by the
        ordinal its events carry.  Same dedup/snapshot semantics as
        :meth:`note_rank_failure`."""
        if not isinstance(error, _failure_types()):
            return
        ev = CommEvent(
            seq=next(self._seq), rank=rank, world=world_ord,
            world_size=world_size, channel="exchange",
            op=f"({type(error).__name__})",
            status=type(error).__name__)
        self._note_failure(ev, error)

    # ------------------------------------------------------------- Mode A

    def record_spmd(self, label: str, nbytes: int) -> None:
        """Host-callback target of :func:`spmd_collective_event` — one
        step-level Mode A event per executed collective entry (per
        device under a multi-device lowering: each shard's runtime
        really entered the collective)."""
        ev = CommEvent(
            seq=next(self._seq), rank=-1, world=-1, world_size=0,
            channel="spmd", op=label, signature=(label,),
            payload_bytes=int(nbytes), t_start=time.perf_counter())
        self._append(ev)

    # -------------------------------------------------------------- reads

    def absorb(self, world, shards: List[Optional[dict]]) -> None:
        """Merge process-backend worker tracer dumps into THIS tracer —
        the parent-side half of the transport's observability contract
        (``reconcile`` over a process-backend trace must read EXACTLY
        like a thread-backend one).

        ``shards[rank]`` is the worker's shipped dump (``{"events",
        "postmortems", "dropped"}``) or None.  Events are re-sequenced
        into the parent's program order by their start timestamps
        (``perf_counter`` shares one monotonic base across processes on
        one host) under the parent's ordinal for ``world``; per-world
        postmortems dedup-merge exactly like concurrent observers of
        one tear do (first snapshot wins, later shards add their
        observers and their own rank's ring tail)."""
        ord_ = self._world_ord(world)
        merged: List[CommEvent] = []
        for sh in shards:
            if not sh:
                continue
            self.dropped += int(sh.get("dropped") or 0)
            merged.extend(sh.get("events") or ())
        merged.sort(key=lambda ev: ev.t_start)
        for ev in merged:
            self._append(dataclasses.replace(
                ev, seq=next(self._seq), world=ord_))
        for sh in shards:
            if not sh:
                continue
            for pm in sh.get("postmortems") or ():
                self._absorb_postmortem(ord_, pm)

    def _absorb_postmortem(self, ord_: int, pm: dict) -> None:
        with self._lock:
            idx = self._failed_worlds.get(ord_)
            if idx is None:
                pm = dict(pm)
                pm["world"] = ord_
                pm["tails"] = dict(pm.get("tails") or {})
                self._failed_worlds[ord_] = len(self.postmortems)
                self.postmortems.append(pm)
                return
            dst = self.postmortems[idx]
            dst["observers"] += pm.get("observers", 1)
            dst["observer_ranks"] = sorted(
                set(dst["observer_ranks"])
                | set(pm.get("observer_ranks") or ()))
            for r, tail in (pm.get("tails") or {}).items():
                dst["tails"][r] = tail
            if not dst.get("failed_ranks") and pm.get("failed_ranks"):
                dst["failed_ranks"] = pm["failed_ranks"]

    def events_for(self, rank: Optional[int] = None,
                   channel: Optional[str] = None) -> List[CommEvent]:
        with self._lock:
            evs = list(self.events)
        if rank is not None:
            evs = [e for e in evs if e.rank == rank]
        if channel is not None:
            evs = [e for e in evs if e.channel == channel]
        return evs

    def tails(self) -> Dict[tuple, List[CommEvent]]:
        """Per-(world, rank) flight-recorder ring contents (newest
        last)."""
        with self._lock:
            return {k: list(r) for k, r in self._rings.items()}

    def last_postmortem(self) -> Optional[dict]:
        with self._lock:
            return self.postmortems[-1] if self.postmortems else None

    def clear(self) -> None:
        with self._lock:
            self.events.clear()
            self.dropped = 0
            self._rings.clear()
            self.postmortems.clear()
            self._failed_worlds.clear()


def current_tracer() -> Optional[CommTracer]:
    """The installed tracer (or None) — ``config.comm_tracer`` re-read."""
    return _config.comm_tracer()


@contextmanager
def trace(ring: int = 64, max_events: int = 200_000,
          mode_a: bool = False, tracer: Optional[CommTracer] = None):
    """Install a :class:`CommTracer` for the block and yield it::

        with mpi.obs.trace() as t:
            mpi.run_ranks(step, 8)
        report = mpi.obs.reconcile(t, lowered)   # reads t.dropped too

    Process-wide like the fault plan (events must flow from
    ``run_ranks`` rank threads, which a thread-local scope opened
    outside them would miss); the previous tracer is restored on exit.
    ``mode_a=True`` additionally instruments Mode A lowerings traced
    inside the block (and retraces them, via the thresholds
    fingerprint)."""
    t = tracer if tracer is not None else CommTracer(
        ring=ring, max_events=max_events, mode_a=mode_a)
    prev = _config.comm_tracer()
    _config.set_comm_tracer(t)
    try:
        yield t
    finally:
        _config.set_comm_tracer(prev)


def spmd_collective_event(x, where: str):
    """Mode A step-event hook (the ``spmd_finite_value`` precedent):
    called at trace time on a collective entry's input value.  With no
    tracer installed — or ``mode_a=False`` (default) — returns ``x``
    untouched: ZERO ops added, the lowering is bit-identical to an
    obs-less build (tests/test_obs.py holds it).  With
    ``mode_a=True``, attaches a host callback that records one
    step-level event per execution, carrying the statically-known
    payload bytes."""
    tracer = _config.comm_tracer()
    if tracer is None or not tracer.mode_a:
        return x
    import functools

    import jax
    import jax.numpy as jnp

    xa = jnp.asarray(x)
    nbytes = int(xa.size) * xa.dtype.itemsize
    # Anchor the callback on one element so it is ordered with (and not
    # DCE'd away from) the collective it reports, without shipping the
    # whole payload to the host.
    anchor = xa.reshape(-1)[:1] if xa.size else jnp.zeros((1,), xa.dtype)
    jax.debug.callback(
        functools.partial(_spmd_emit, where=where, nbytes=nbytes), anchor)
    return x


def _spmd_emit(_anchor, *, where: str, nbytes: int) -> None:
    tracer = _config.comm_tracer()
    if tracer is not None:
        tracer.record_spmd(where, nbytes)
