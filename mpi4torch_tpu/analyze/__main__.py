"""`python -m mpi4torch_tpu.analyze` — the analyze-smoke lane.

``--sweep``
    Registry-wide lint sweep (:mod:`.sweep`): every registered
    (algorithm × codec) Allreduce pair (forward + backward, with the
    VJP-symmetry declaration checked), the Bcast_/Reduce_ algorithm
    forms, every reshard strategy, the overlap schedules, and the
    serve decode step, lowered on the attached mesh and run through
    the full soundness lint set — plus the standing registry-sync
    guards.  Exits non-zero on ANY lint violation or registry drift.

``--defects``
    Seeded-defect corpus (:mod:`.defects`): mutated schedules —
    dropped wait, orphan/double wait, duplicated permute target,
    non-partitioning replica group, dropped backward — each of which
    must be caught BY ITS NAMED LINT, with the ledger check that every
    registered lint catches at least one mutant.  Exits non-zero when
    a lint fails to fire (a lint without a firing mutant reads as
    coverage but checks nothing).

The Makefile's ``analyze-smoke`` target runs both on the
8-virtual-device CPU mesh.
"""

from __future__ import annotations

import sys


def _corpus_programs():
    """Build the clean programs the defect corpus mutates, on the
    attached multi-device mesh: a windowed split-phase program, a
    permute-schedule program (bidir's dual ring), a grouped program
    (ring reduce-scatter + all-gather), and a ring forward /
    forward+backward pair."""
    import jax
    import jax.numpy as jnp

    import mpi4torch_tpu as mpi
    from .defects import DefectPrograms
    from .sweep import _flat_lowerer
    from ..overlap import overlap_split_allreduce

    n = len(jax.devices())
    if n < 2:
        raise RuntimeError(
            "the defect corpus mutates multi-device schedules; run via "
            "`make analyze-smoke` (8-virtual-device CPU mesh)")
    lower, comm = _flat_lowerer(n)
    x = jnp.ones((512,), jnp.float32)

    split = lower(lambda c, v: overlap_split_allreduce(
        c, v, mpi.MPI_SUM, nsplits=2), x)
    permute = lower(lambda c, v: c.Allreduce(v, mpi.MPI_SUM,
                                             algorithm="bidir"), x)
    grouped = lower(lambda c, v: c.Reduce_scatter(v, mpi.MPI_SUM, 0),
                    x)
    fwd = lower(lambda c, v: c.Allreduce(v, mpi.MPI_SUM), x)
    fwdbwd = lower(
        lambda c, v: jax.value_and_grad(
            lambda u: jnp.sum(c.Allreduce(u, mpi.MPI_SUM)))(v), x)
    return DefectPrograms(split_phase=split, permute=permute,
                          grouped=grouped, fwd=fwd, fwdbwd=fwdbwd)


def _defects() -> int:
    from .defects import defect_ledger_problems, run_defect_corpus

    records = run_defect_corpus(_corpus_programs())
    failures = 0
    for rec in records:
        ok = rec["clean_ok"] and rec["fired"]
        tag = f"{rec['defect']} -> {rec['lint']}"
        if ok:
            print(f"ok  : {tag}: fired ({rec['doc']})")
        else:
            failures += 1
            print(f"FAIL: {tag}: clean_ok={rec['clean_ok']} "
                  f"fired={rec['fired']}")
    for p in defect_ledger_problems(records):
        failures += 1
        print(f"FAIL[ledger]: {p}")
    print(f"defect corpus: {len(records)} mutants, "
          f"{failures} failure(s)")
    if failures:
        return 1
    print("defect corpus: OK — every lint fires on its mutant")
    return 0


def _sweep() -> int:
    import jax

    from .sweep import run_sweep, sweep_worlds

    ndev = len(jax.devices())
    print(f"analyze-sweep: {ndev} device(s), platform "
          f"{jax.devices()[0].platform}")
    failures = 0
    for world in sweep_worlds(ndev):
        # The serve decode leg compiles real engine steps; once, on
        # the full world, is the meaningful cell.
        res = run_sweep(world, include_serve=(world == (ndev,)))
        for rec in res["records"]:
            if rec["skipped"]:
                print(f"skip: {rec['case']}: {rec['skipped']}")
            elif rec["violations"]:
                failures += len(rec["violations"])
                for v in rec["violations"]:
                    print(f"FAIL: {rec['case']}: {v}")
            else:
                extra = ""
                if "scheduled_exposure" in rec:
                    extra = (" exposure="
                             f"{rec['scheduled_exposure']}")
                census = ",".join(f"{k}={v}"
                                  for k, v in rec["census"].items())
                print(f"ok  : {rec['case']}: "
                      f"[{census or 'no collectives'}]{extra}")
        for p in res["problems"]:
            failures += 1
            print(f"FAIL[registry]: {p}")
        print(f"world {world}: {res['n_cases']} cases linted, "
              f"{res['n_skipped']} skipped, "
              f"{len(res['violations'])} violation(s)")
    if failures:
        print(f"analyze-sweep: {failures} FAILURE(S)")
        return 1
    print("analyze-sweep: OK — every registered schedule lints clean")
    return 0


def main(argv) -> int:
    rc = 0
    ran = False
    if "--sweep" in argv:
        ran = True
        rc |= _sweep()
    if "--defects" in argv:
        ran = True
        rc |= _defects()
    if not ran:
        print(__doc__)
    return rc


if __name__ == "__main__":
    from mpi4torch_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    sys.exit(main(sys.argv[1:]))
