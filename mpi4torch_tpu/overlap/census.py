"""Scheduled-exposure census: quantify, from a lowered program, how much
of its bucket communication the schedule leaves *exposed*.

Wall-clock exposed-comm measurements need hardware whose collective
runtime is actually asynchronous; on the CPU smoke mesh the in-process
rendezvous executes synchronously on the device threads, so blocking
and split-phase programs time within scheduler noise of each other.
What IS deterministic on
every platform is the *schedule itself*: the lowered program either
gives the runtime something to hide a transfer behind, or it does not.

:func:`scheduled_exposure` parses a ``debug_info`` lowering (the
``jax.named_scope`` spans of :func:`~mpi4torch_tpu.utils.profiling.
bucket_scope` survive into the StableHLO location table) and classifies
every ``mpi4torch.<Op>.bucket<i>of<n>`` collective:

* a bucket whose scope carries the split-phase ``.start``/``.wait``
  suffixes owns a *window* — the span between its last start-phase op
  and its first wait-phase op.  If another collective's wire op lands
  inside that window, the transfer has in-flight company the runtime
  can overlap it with: **hidden**.  An empty window (nothing else in
  flight) is **exposed** — the schedule serialized it after all.
* a bucket with no phase suffix is a blocking collective: start and
  completion coincide, the window is zero-width, and the transfer is
  exposed by construction (the 100%-exposed baseline
  utils/profiling.bucket_scope documents).

The census is exact about the program, conservative about the runtime:
it never claims wall-clock hiding, only that the schedule keeps >= 2
transfers in flight (the same invariant tests/test_overlap.py's
ordering censuses assert op-by-op, folded down to one fraction).
Blocking programs census to 1.0, windowed split-phase programs
strictly lower (tests/test_overlap.py ``TestScheduledExposure``).

Since the static verifier landed (:mod:`mpi4torch_tpu.analyze`), the
parsing and the window classification live there as a pass over the
shared StableHLO parse — this module keeps the historical entry point
(and its recorded fractions, regression-pinned bit-identical in
tests/test_analyze.py) as a delegation.
"""

from __future__ import annotations

from typing import Dict

from ..analyze.accounting import scheduled_exposure as _scheduled_exposure
from ..analyze.parse import WIRE_OPS

__all__ = ["scheduled_exposure", "WIRE_OPS"]


def scheduled_exposure(lowered_or_text) -> Dict:
    """Census a lowering (a ``jax.stages.Lowered`` or its
    ``debug_info=True`` text) for scheduled communication exposure.

    Returns ``{"n_buckets", "n_exposed", "exposed_fraction", "buckets"}``
    where ``buckets`` maps ``"<Op>.bucket<i>of<n>"`` to
    ``{"split_phase": bool, "exposed": bool}``.  ``exposed_fraction`` is
    ``None`` when the program contains no bucket collectives (e.g. a
    single-device world whose collectives lowered away)."""
    return _scheduled_exposure(lowered_or_text)
