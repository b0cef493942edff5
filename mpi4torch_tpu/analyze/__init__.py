"""mpi4torch_tpu.analyze — the static collective-schedule verifier.

The paper's core contract — every collective is an AD node whose
backward is itself a collective, with handle machinery encoding the
cross-rank ordering the per-rank DAG cannot see — is exactly the class
of property a static pass can verify *before* the wire runs (GC3,
PAPERS.md: collective schedules are programs you can analyze and
transform).  This package is that pass, in four layers:

* **one parser** (:mod:`.parse`): :func:`parse_program` turns any
  lowered program into typed :class:`CollectiveOp` records — kind,
  ``replica_groups``, ``source_target_pairs``, channel, payload
  dtype/bytes, and the named-scope label recovered from the debug-info
  loc table — replacing the regex censuses that had grown in
  overlap/census.py, reshard/census.py and tests/.
* **soundness lints** (:mod:`.lints`): permute tables form valid
  partial permutations, replica groups exactly partition the
  participating axis, split-phase start→wait spans pair up per bucket
  with no dangling or double-completed handle, and each registered
  algorithm's backward census is its declared transpose
  (``AlgorithmSpec.vjp_census``) — today's runtime-only failure modes
  (DeadlockError, BifurcationError, silent corruption) as trace-time
  diagnoses.
* **unified accounting** (:mod:`.accounting`):
  :func:`wire_bytes_per_device`, :func:`peak_live_bytes`,
  :func:`scheduled_exposure` re-expressed on the shared parse; the
  historical entry points delegate here and their recorded BENCH/smoke
  numbers are regression-pinned bit-identical.
* **the registry-wide sweep** (:mod:`.sweep`, ``python -m
  mpi4torch_tpu.analyze --sweep``): lowers every registered
  (algorithm × codec) pair, reshard strategy, and overlap/serve decode
  schedule on the attached mesh and fails non-zero on any lint
  violation; the **seeded-defect corpus** (:mod:`.defects`,
  ``--defects``) proves every lint fires on a mutated schedule — the
  fired-fault-ledger discipline, applied to static analysis.

:mod:`.registry` additionally hosts the deduped registry-sync guards
every subsystem's smoke lane and test file had been carrying as
copies.  ``make analyze-smoke`` runs sweep + defect corpus on the
8-virtual-device CPU mesh.  See doc/analysis.md.
"""

from .accounting import (peak_live_bytes, scheduled_exposure,
                         tier_wire_table, weighted_wire_cost,
                         wire_bytes_per_device, wire_contribution)
from .defects import (DEFECTS, Defect, DefectPrograms,
                      defect_ledger_problems, run_defect_corpus)
from .lints import (LINT_NAMES, LintViolation, check_vjp_symmetry,
                    run_lints)
from .parse import (COLLECTIVE_KINDS, WIRE_OPS, CollectiveOp, OpEvent,
                    ParsedProgram, bucket_of, parse_program,
                    tensor_bytes)
from .sweep import run_sweep, sweep_worlds

__all__ = [
    "COLLECTIVE_KINDS",
    "WIRE_OPS",
    "CollectiveOp",
    "OpEvent",
    "ParsedProgram",
    "bucket_of",
    "parse_program",
    "tensor_bytes",
    "LINT_NAMES",
    "LintViolation",
    "run_lints",
    "check_vjp_symmetry",
    "wire_bytes_per_device",
    "wire_contribution",
    "tier_wire_table",
    "weighted_wire_cost",
    "peak_live_bytes",
    "scheduled_exposure",
    "DEFECTS",
    "Defect",
    "DefectPrograms",
    "run_defect_corpus",
    "defect_ledger_problems",
    "run_sweep",
    "sweep_worlds",
]
