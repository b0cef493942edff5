"""`python -m mpi4torch_tpu.reshard --smoke` — the reshard-smoke lane.

An 8-virtual-device sweep (the Makefile's ``reshard-smoke`` target) of
representative (mesh, spec) -> (mesh', spec') transitions.  Every cell:

1. the compiled Mode A result is compared BITWISE against two oracles —
   the numpy assemble-and-slice reference and the executed
   gather-then-slice baseline strategy;
2. the lowered StableHLO of the planned program is censused: its peak
   live bytes (:func:`mpi4torch_tpu.reshard.peak_live_bytes`) must be
   STRICTLY below the gather baseline's — the memory-bounded claim as a
   deterministic inequality, not a wall-clock anecdote;
3. one cell re-runs under ``deterministic_mode`` and one runs its VJP
   (cotangents must land as the reverse redistribution).

Plus the registry-sync guard: the step-kind registry, both executor
dispatch tables, the adjoint closure, and the kinds actually exercised
by the sweep (forward + adjoint plans) must agree — a step kind without
coverage fails the lane.  Exits non-zero on any divergence.
"""

from __future__ import annotations

import sys


def _cases(n: int, factors):
    from . import layout

    cases = [
        ("axis-move", layout((n,), 0, None), layout((n,), None, 0), None),
        ("replicate", layout((n,), 0, None), layout((n,), None, None),
         None),
        ("slice", layout((n,), None, None), layout((n,), 0, None), None),
    ]
    if factors is not None:
        a, b = factors
        cases += [
            ("migrate", layout((n,), 0, None), layout((a, b), 0, 1),
             None),
            ("migrate-T", layout((n,), 0, None), layout((b, a), 0, 1),
             None),
            ("migrate-rounds", layout((n,), 0, None),
             layout((a, b), 0, 1), "rounds"),
            ("coarsen", layout((n,), 0, None), layout((a, b), (0,), None),
             None),
            ("refine", layout((a, b), (0,), None), layout((n,), 0, None),
             None),
            ("block-permute", layout((a, b), (0, 1), None),
             layout((a, b), (1, 0), None), None),
            ("zero-to-tp", layout((n,), 0, None),
             layout((a, b), None, 1), None),
        ]
    return cases


def _smoke() -> int:
    import numpy as np

    import jax
    import jax.numpy as jnp

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import reshard as rs
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    n = len(jax.devices())
    print(f"reshard-smoke: {n} device(s), platform "
          f"{jax.devices()[0].platform}")
    if n < 2:
        print("FAIL: the sweep needs a multi-device world — run via "
              "`make reshard-smoke` (8-virtual-device CPU mesh)")
        return 1
    factors = None
    for a in range(2, n):
        if n % a == 0 and n // a > 1:
            factors = (a, n // a)
            break

    G = (2 * n * 2, n)                       # divisible by every factor
    rng = np.random.default_rng(0)
    full = rng.standard_normal(G).astype(np.float32)

    mesh = Mesh(np.asarray(jax.devices()), ("w",))
    comm = mpi.comm_from_mesh(mesh, "w")

    def np_shard(lay, r):
        return np.asarray(rs.slice_shard(full, lay, r))

    def run_mode_a(fl, tl, strategy, det=False):
        shard = fl.shard_shape(G)
        starts = np.asarray(
            [[b * s for b, s in zip(fl.block(r), shard)]
             for r in range(n)])

        def body():
            c = mpi.COMM_WORLD
            row = jnp.asarray(starts)[jnp.asarray(c.rank + 0)]
            sl = jax.lax.dynamic_slice(
                jnp.asarray(full), tuple(row[i] for i in range(2)), shard)
            with mpi.config.deterministic_mode(det):
                return c.Reshard(sl, fl, tl, strategy=strategy)

        return np.asarray(mpi.run_spmd(body, nranks=n)())

    def lowered(fl, tl, strategy):
        fn = shard_map(
            lambda a: comm.Reshard(a, fl, tl, strategy=strategy),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
        return jax.jit(fn).lower(
            jnp.zeros(fl.shard_shape(G), jnp.float32)).as_text()

    exercised = set()
    failures = 0
    for name, fl, tl, strategy in _cases(n, factors):
        plan = rs.plan_reshard(fl, tl, G, np.float32, strategy)
        adj = plan.adjoint()
        exercised |= {s.kind for s in plan.steps}
        exercised |= {s.kind for s in adj.steps}
        gplan = rs.plan_reshard(fl, tl, G, np.float32, "gather")
        exercised |= {s.kind for s in gplan.steps}
        exercised |= {s.kind for s in gplan.adjoint().steps}

        got = run_mode_a(fl, tl, strategy)
        oracle_np = np.stack([np_shard(tl, r) for r in range(n)])
        oracle_gather = run_mode_a(fl, tl, "gather")
        ok = (np.array_equal(got, oracle_np)
              and np.array_equal(oracle_gather, oracle_np))
        peak_p = rs.peak_live_bytes(lowered(fl, tl, strategy))
        peak_g = rs.peak_live_bytes(lowered(fl, tl, "gather"))
        bounded = (plan.strategy == "gather") or peak_p < peak_g
        if not ok or not bounded:
            failures += 1
            print(f"FAIL {name}: bitwise={ok} peak {peak_p} vs "
                  f"gather {peak_g} (strategy {plan.strategy})")
            continue
        print(f"cell {name:14s} strategy={plan.strategy:9s} "
              f"steps={[s.kind for s in plan.steps]} bitwise=ok "
              f"peak_live {peak_p} < gather {peak_g}")

    # Deterministic-mode leg on the migration cell.
    if factors is not None:
        fl = rs.layout((n,), 0, None)
        tl = rs.layout(factors, 0, 1)
        got = run_mode_a(fl, tl, None, det=True)
        if not np.array_equal(
                got, np.stack([np_shard(tl, r) for r in range(n)])):
            failures += 1
            print("FAIL: deterministic_mode migration diverges")
        else:
            print("cell migrate/deterministic_mode bitwise=ok")

        # VJP leg: cotangents must redistribute spec' -> spec (run on
        # the eager world, where each rank holds a concrete shard).
        w = rng.standard_normal((n,) + tl.shard_shape(G)).astype(
            np.float32)

        def egbody():
            c = mpi.COMM_WORLD
            sl = jnp.asarray(np_shard(fl, c.rank))
            wr = jnp.asarray(w)[c.rank]
            return jax.grad(
                lambda v: jnp.vdot(c.Reshard(v, fl, tl), wr))(sl)

        g = mpi.run_ranks(egbody, n)
        wfull = np.zeros(G, np.float32)
        sh = tl.shard_shape(G)
        for r in range(n):
            blk = tl.block(r)
            wfull[tuple(slice(b * s, (b + 1) * s)
                        for b, s in zip(blk, sh))] = w[r]
        ok = all(
            np.array_equal(np.asarray(g[r]), np_shard_of(wfull, fl, r))
            for r in range(n))
        if not ok:
            failures += 1
            print("FAIL: VJP cotangents did not redistribute "
                  "spec' -> spec")
        else:
            print("cell migrate/vjp: cotangents redistribute "
                  "spec'->spec bitwise")

    # Registry-sync guard (the shared checker in
    # mpi4torch_tpu.analyze.registry; messages unchanged).
    from mpi4torch_tpu.analyze.registry import reshard_step_problems

    kinds = set(rs.STEP_KINDS)
    probs = reshard_step_problems(exercised)
    if probs:
        failures += 1
        print("FAIL registry-sync: " + "; ".join(probs))
    else:
        print(f"registry-sync: {len(kinds)} step kinds == both "
              "executors == sweep coverage (fwd+adjoint)")

    if failures:
        print(f"reshard-smoke: {failures} FAILURE(S)")
        return 1
    print("reshard-smoke: OK")
    return 0


def np_shard_of(arr, lay, r):
    import numpy as np

    from . import slice_shard

    return np.asarray(slice_shard(arr, lay, r))


def main(argv) -> int:
    if "--smoke" in argv:
        return _smoke()
    print(__doc__)
    return 0


if __name__ == "__main__":
    from mpi4torch_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    sys.exit(main(sys.argv[1:]))
