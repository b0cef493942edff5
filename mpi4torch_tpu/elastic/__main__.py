"""`python -m mpi4torch_tpu.elastic --smoke` — the elastic-smoke lane.

Runs the FULL elastic matrix (:mod:`.matrix`): every (failure kind ×
subsystem × action) cell — rank_death and preempt across the plain /
ZeRO / MoE / serve subsystems under shrink, grow-after-shrink and
hot-spare takeover — plus the two membership-failure cells (injected
proposal disagreement; a rank dying mid-consensus).  A cell passes only
when it ends **recovered and bitwise against the fresh-start oracle on
the new world** (the fired-fault ledger proving the fault acted — no
vacuous passes) or in its typed, rank-attributed raise.  Exits non-zero
on ANY hang-shaped failure, unattributed error, non-bitwise recovery,
unfired cell, or registry drift (``analyze.registry.elastic_problems``
— the PR 4/6/7 registry-sync guard applied to the elastic coverage
table).

The Makefile's ``elastic-smoke`` target runs it on the 8-virtual-device
CPU harness.
"""

from __future__ import annotations

import sys


def _check_registry_sync() -> list:
    from ..analyze.registry import elastic_problems

    return elastic_problems()


def _smoke() -> int:
    import jax

    from .matrix import (CONSENSUS_COVERAGE, COVERAGE, run_cell,
                         run_consensus_cell)

    ndev = len(jax.devices())
    print(f"elastic-smoke: {ndev} device(s), platform "
          f"{jax.devices()[0].platform}, "
          f"{len(COVERAGE) + len(CONSENSUS_COVERAGE)} cells")

    problems = _check_registry_sync()
    for p in problems:
        print(f"FAIL[registry]: {p}")

    failures = len(problems)
    ran = 0
    for kind, subsystem, action in sorted(COVERAGE):
        rec = run_cell(kind, subsystem, action)
        ran += 1
        tag = f"{kind} x {subsystem} x {action}"
        if rec.get("fallback"):
            tag += " (fallback)"
        if rec["status"] == "ok":
            print(f"ok  : {tag}: {rec['detail']}")
        else:
            failures += 1
            print(f"FAIL: {tag}: {rec['detail']}")

    for kind, subsystem, action in sorted(CONSENSUS_COVERAGE):
        rec = run_consensus_cell(kind)
        ran += 1
        tag = f"{kind} x {subsystem}"
        if rec["status"] == "ok":
            print(f"ok  : {tag}: {rec['detail']}")
        else:
            failures += 1
            print(f"FAIL: {tag}: {rec['detail']}")

    print(f"elastic-smoke: {ran} cells, {failures} failure(s)")
    if failures:
        return 1
    print("elastic-smoke: OK — every cell recovered bitwise on the new "
          "world or raised typed+attributed; no hangs, no unfired "
          "cells")
    return 0


def main(argv) -> int:
    if "--smoke" in argv:
        return _smoke()
    print(__doc__)
    return 0


if __name__ == "__main__":
    from mpi4torch_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    sys.exit(main(sys.argv[1:]))
