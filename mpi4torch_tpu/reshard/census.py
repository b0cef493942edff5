"""Deterministic peak-live-bytes census of a lowered StableHLO program.

The repo's perf-evidence currency is deterministic estimators read off
the lowering (HLO op counts, wire bytes, scheduled exposure — ROADMAP);
this module adds the memory leg: a last-use liveness scan over the
module's SSA values.  Each ``%v = op ... : ... -> tensor<...>`` line
defines a value of known byte size; a value stays live from its
definition to its last textual use; the census is the maximum over
program points of the live-set byte total (function arguments included).

This is an *estimator* — XLA's buffer assignment can alias and fuse —
but it is exact about what the planner controls: a program that
materializes an ``N x shard`` gather carries an N-times-shard tensor
through its liveness range no matter how it is scheduled, while the
planned exchange never defines one.  Planned-vs-gather comparisons run
both programs through the same scan, so systematic bias cancels; the
``peak_memory_bounded`` verdict (`make reshard-smoke`,
tests/test_analyze.py ``TestReshardCensusRegression``) is the strict
inequality between the two.

Since the static verifier landed (:mod:`mpi4torch_tpu.analyze`), the
scan itself lives there as a pass over the shared StableHLO parse
(per-``func.func`` scoping and all) — this module keeps the historical
entry points (and their recorded census values, regression-pinned
bit-identical in tests/test_analyze.py) as delegations.
"""

from __future__ import annotations

from ..analyze.accounting import peak_live_bytes as _peak_live_bytes
from ..analyze.parse import tensor_bytes

__all__ = ["peak_live_bytes", "tensor_bytes"]


def peak_live_bytes(txt: str) -> int:
    """Max over program points of the summed byte sizes of live SSA
    values (see module docstring).  SSA names are per-function scopes,
    so the module is censused function by function and the maximum
    wins (the shard_map body is where the collectives live)."""
    return _peak_live_bytes(txt)
