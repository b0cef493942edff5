"""`python -m mpi4torch_tpu.resilience --smoke|--chaos` — the
faults-smoke and chaos-smoke lanes.

Runs the FULL fault matrix (:mod:`.matrix`): every registered fault
kind × one representative collective per subsystem (plain / fused /
compressed / overlap, plus the checkpoint cell), on the ``(3,)``,
``(8,)`` and (2,4)-factorized torus worlds.  A cell passes only when
its fault is *recovered* (transient, bitwise-exact under the configured
retries), *detected* (its typed, rank-attributed error), or *provably
inert* (no eligible target AND a bitwise-exact result) — exits non-zero
if ANY fault goes undetected, unattributed, or silently corrupts, and
if the fault-kind registry and the coverage table have drifted apart
(the PR 4/6 registry-sync guard, enforced structurally here and in
tests/test_resilience.py).

``--chaos`` runs the GRAY-failure matrix instead (:mod:`.chaos`,
``make chaos-smoke``): every (gray kind × {plain, fused, compressed,
overlap, serve, elastic}) cell plus seeded multi-fault storms — each
cell must end recovered-BITWISE, degraded-with-attributed-report
(epoch-fenced lock-step transition), or in its typed attributed raise,
NEVER a hang; the fired-fault ledger must show every gray kind acted
somewhere, and the degrade-policy registry guard runs first.

The Makefile's ``faults-smoke``/``chaos-smoke`` targets run these on
the 8-virtual-device CPU harness.
"""

from __future__ import annotations

import sys


def _check_registry_sync() -> list:
    # The checker body moved to the shared registry-guard home
    # (mpi4torch_tpu.analyze.registry) with its messages intact; this
    # name stays as THE entry point the smoke lane and
    # tests/test_resilience.py share.
    from ..analyze.registry import resilience_problems

    return resilience_problems()


def _smoke() -> int:
    import tempfile

    import jax

    from .matrix import (COVERAGE, WORLDS, coverage_cells, run_cell,
                         run_checkpoint_cell)

    ndev = len(jax.devices())
    print(f"faults-smoke: {ndev} device(s), platform "
          f"{jax.devices()[0].platform}, "
          f"{len(COVERAGE)} fault kinds")

    problems = _check_registry_sync()
    for p in problems:
        print(f"FAIL[registry]: {p}")

    failures = len(problems)
    ran = 0
    for nranks, algorithm in WORLDS:
        world = f"({nranks},)" if algorithm is None \
            else f"({nranks} as 2-level torus)"
        for kind, subsystem in coverage_cells():
            if subsystem == "checkpoint":
                continue  # world-independent; run once below
            if algorithm is not None and subsystem not in (
                    "plain", "compressed"):
                # The torus leg exercises the 2-level schedule — only
                # the cells that take an algorithm argument ride it.
                continue
            rec = run_cell(kind, subsystem, nranks=nranks,
                           algorithm=algorithm)
            ran += 1
            tag = f"{kind} x {subsystem} @ {world}"
            if rec["status"] == "ok":
                print(f"ok  : {tag}: {rec['detail']}")
            else:
                failures += 1
                print(f"FAIL: {tag}: {rec['detail']}")

    try:
        import orbax.checkpoint  # noqa: F401
        with tempfile.TemporaryDirectory() as d:
            rec = run_checkpoint_cell(d)
        ran += 1
        tag = "truncate_save x checkpoint"
        if rec["status"] == "ok":
            print(f"ok  : {tag}: {rec['detail']}")
        else:
            failures += 1
            print(f"FAIL: {tag}: {rec['detail']}")
    except ModuleNotFoundError:
        print("skip: truncate_save x checkpoint (orbax not installed)")

    print(f"faults-smoke: {ran} cells, {failures} failure(s)")
    if failures:
        return 1
    print("faults-smoke: OK — every fault recovered, typed+attributed, "
          "or provably inert; no silent corruption")
    return 0


def _chaos() -> int:
    import jax

    from ..analyze.registry import degrade_problems
    from .chaos import GRAY_KINDS, coverage_cells, run_chaos_cell, \
        run_storm

    ndev = len(jax.devices())
    print(f"chaos-smoke: {ndev} device(s), platform "
          f"{jax.devices()[0].platform}, gray kinds {GRAY_KINDS}")

    problems = degrade_problems()
    for p in problems:
        print(f"FAIL[registry]: {p}")
    failures = len(problems)

    ran = 0
    fired_kinds = set()
    for kind, subsystem in coverage_cells():
        rec = run_chaos_cell(kind, subsystem)
        ran += 1
        fired_kinds.update(rec.get("fired", []))
        tag = f"{kind} x {subsystem} [{rec['expected']}]"
        if rec["status"] == "ok":
            print(f"ok  : {tag}: {rec['detail']}")
        else:
            failures += 1
            print(f"FAIL: {tag}: {rec['detail']}")

    for seed in (1, 2):
        rec = run_storm(seed)
        ran += 1
        fired_kinds.update(rec.get("fired", []))
        if rec["status"] == "ok":
            print(f"ok  : storm seed={seed}: {rec['detail']}")
        else:
            failures += 1
            print(f"FAIL: storm seed={seed}: {rec['detail']}")

    unacted = set(GRAY_KINDS) - fired_kinds
    if unacted:
        failures += 1
        print(f"FAIL[ledger]: gray kind(s) {sorted(unacted)} never "
              "fired anywhere — the matrix is vacuous for them")

    print(f"chaos-smoke: {ran} cells, {failures} failure(s)")
    if failures:
        return 1
    print("chaos-smoke: OK — every gray cell recovered bitwise, "
          "degraded with an attributed epoch-fenced transition, or "
          "raised typed+attributed; no hangs, every kind acted")
    return 0


def main(argv) -> int:
    if "--chaos" in argv:
        return _chaos()
    if "--smoke" in argv:
        return _smoke()
    print(__doc__)
    return 0


if __name__ == "__main__":
    from mpi4torch_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    sys.exit(main(sys.argv[1:]))
