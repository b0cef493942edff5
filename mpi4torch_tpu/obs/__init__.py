"""mpi4torch_tpu.obs — unified runtime observability.

The stack had rich *static* evidence (the ``analyze`` wire/peak/
exposure accountings) and scattered *runtime* counters
(``World.retry_events``, the guards' violation ledger, ``ServeStats``)
but no unified runtime layer: no wire timeline, no metrics export, no
postmortem of what the chokepoints actually did when a rank died.
This package is that layer, in five pieces:

* **chokepoint comm tracing** (:mod:`.trace`, :mod:`.events`) — typed
  :class:`CommEvent` records emitted at the two Mode B chokepoints
  every subsystem funnels through (``World.exchange`` + the p2p
  mailboxes: fuse/compress/overlap/reshard/serve traffic traced with
  zero per-subsystem hooks), plus Mode A step events via the
  named-scope/host-callback hook.  Off path: one attribute read per
  rendezvous, lowering bit-identical to an obs-less build (held by
  tests/test_obs.py).
* a **metrics registry** (:mod:`.metrics`) — thread-safe counters/
  gauges/histograms with JSON snapshot and Prometheus text export,
  absorbing the ad-hoc surfaces (retry events, integrity violations,
  autotuner cache hits, serve counters) under one ``mpi4torch_*``
  namespace; also the shared :func:`percentile` rule and the weakref
  stats-source registry ``ServeStats`` aggregation re-homed onto.
* a **flight recorder** (:mod:`.flight`) — bounded per-rank rings of
  recent events, dumped as a rank-attributed postmortem (JSON + human
  table) when ``RankFailedError``/``DeadlockError``/``IntegrityError``
  is raised: the last N wire operations on each rank when it died.
* **Chrome-trace/Perfetto export** (:mod:`.export`) of the Mode B
  timeline, next to the existing ``utils.profiler_trace`` xplane
  capture.
* **static-vs-runtime reconciliation** (:mod:`.reconcile`) —
  :func:`reconcile` joins measured wire bytes / event counts against
  ``analyze.wire_bytes_per_device`` predictions, exact-match
  deterministic on Mode B (bytes are censused, not sampled): a
  CI-checkable contract, not a dashboard.

``python -m mpi4torch_tpu.obs --smoke`` / ``make obs-smoke`` run the
traced 8-virtual-device lane: reconcile on four representative
schedules, the flight-recorder rank-death postmortem, and the off-path
bit-identity census.  See doc/observability.md.
"""

# Module alias first: the `trace` attribute below is the context
# manager, which shadows the submodule on the package — `obs.tracing`
# is the patchable module handle (an obs-less-build census
# monkeypatches `tracing.spmd_collective_event`).
from . import trace as tracing  # noqa: F401  (module alias)
from .events import CommEvent, annotate_signature, payload_nbytes
from .export import chrome_trace, write_chrome_trace
from .flight import dump_postmortem, format_postmortem
from .metrics import (MetricsRegistry, StatsSourceRegistry, metrics_json,
                      percentile, prometheus_text, register_collector,
                      registry, reset_metrics, snapshot)
from .reconcile import (equivalent_tier_wire, equivalent_wire,
                        measured_wire_table, reconcile)
from .trace import (CommTracer, current_tracer, push_label,
                    spmd_collective_event, trace)

__all__ = [
    "tracing",
    "CommEvent",
    "CommTracer",
    "annotate_signature",
    "payload_nbytes",
    "trace",
    "current_tracer",
    "push_label",
    "spmd_collective_event",
    "MetricsRegistry",
    "StatsSourceRegistry",
    "registry",
    "snapshot",
    "metrics_json",
    "prometheus_text",
    "register_collector",
    "reset_metrics",
    "percentile",
    "format_postmortem",
    "dump_postmortem",
    "chrome_trace",
    "write_chrome_trace",
    "measured_wire_table",
    "reconcile",
    "equivalent_wire",
    "equivalent_tier_wire",
]
