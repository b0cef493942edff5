"""`python -m mpi4torch_tpu.compress --smoke` — the quant-smoke lane.

Exercises the in-schedule quantized pipeline end to end on whatever
devices are attached (the Makefile's ``quant-smoke`` target runs it on
the 8-virtual-device CPU mesh):

1. compressed-bidir BITWISE parity: the compiled Mode A q8 dual-ring
   allreduce against :func:`mpi4torch_tpu.constants.reduce_q8_hop` — the
   eager fold oracle that IS Mode B's side of the parity contract — for
   ``q8`` and the stochastic per-hop-EF ``q8_ef_hop`` codec, plus the
   striped ``torus`` leg on factorable worlds;
2. HLO census: the lowered q8-bidir program must carry int8
   collective_permutes on BOTH source_target_pairs rotations of the
   dual ring (the tentpole's census criterion);
3. hop-kernel equivalence: the Pallas dequant→accumulate→requant kernel
   (interpret mode off-TPU) against the jnp fallback, bit for bit,
   round-to-nearest and stochastic.

Exits non-zero on any divergence, so the lane is a real check, not a
demo.
"""

from __future__ import annotations

import sys


def _smoke() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import constants as C
    from jax import shard_map
    from mpi4torch_tpu.compress import get_codec
    from mpi4torch_tpu.ops import quant_kernels as qk

    comm = mpi.COMM_WORLD
    n = len(jax.devices())
    print(f"quant-smoke: {n} device(s), platform "
          f"{jax.devices()[0].platform}")
    if n < 2:
        print("FAIL: the compressed-bidir check needs a multi-device "
              "world — run via `make quant-smoke` (8-virtual-device "
              "CPU mesh)")
        return 1

    rng = np.random.default_rng(0)
    data = rng.standard_normal((n, 700)).astype(np.float32) * 3.0
    stacked = jnp.asarray(data)
    rows = [jnp.asarray(d) for d in data]
    block = get_codec("q8").base().block

    def spmd(codec, algo):
        def fn(x):
            t = jax.lax.dynamic_index_in_dim(
                x, jnp.asarray(comm.rank + 0), 0, keepdims=False)
            return comm.Allreduce(t, mpi.MPI_SUM, compression=codec,
                                  algorithm=algo)

        return np.asarray(mpi.run_spmd(fn, nranks=n)(stacked))

    combos = [("q8", "bidir", None), ("q8_ef_hop", "bidir", None)]
    try:
        from mpi4torch_tpu.tune import resolve_hier_group

        combos.append(("q8", "torus", resolve_hier_group(n)))
    except Exception:
        print(f"torus leg skipped: {n} ranks have no 2-level "
              "factorization")
    for codec, algo, inner in combos:
        base = get_codec(codec).base()
        got = spmd(codec, algo)
        want = np.asarray(C.reduce_q8_hop(
            rows, block=block, algorithm=algo, inner=inner,
            stochastic=getattr(base, "stochastic", False),
            hop_ef=getattr(base, "hop_ef", False)))
        for r in range(n):
            if not np.array_equal(got[r], want):
                print(f"FAIL: Mode A {codec}-on-{algo} diverges from the "
                      f"fold oracle on rank {r}")
                return 1
        print(f"parity: {codec}-on-{algo} == reduce_q8_hop oracle "
              "(bitwise, all ranks)")

    mesh = Mesh(np.asarray(jax.devices()), ("w",))
    cm = mpi.comm_from_mesh(mesh, "w")
    lowered = jax.jit(shard_map(
        lambda a: cm.Allreduce(a, mpi.MPI_SUM, compression="q8",
                               algorithm="bidir"),
        mesh=mesh, in_specs=P(), out_specs=P(),
        check_vma=False)).lower(jnp.ones((1 << 12,), jnp.float32)).as_text()
    from mpi4torch_tpu.compress import int8_rotation_census

    perms, fwd, bwd = int8_rotation_census(lowered, n)
    if fwd not in perms or bwd not in perms:
        print(f"FAIL: int8 permutes must ride both dual-ring rotations; "
              f"saw {sorted(perms)}")
        return 1
    print("census: int8 collective_permutes on both source_target_pairs "
          "rotations of the q8-bidir dual ring")

    q = jnp.asarray(rng.integers(-127, 128, (300, block)), jnp.int8)
    # wire scales are power-of-two by construction (qk.po2_scale) — the
    # exactness that makes kernel/fallback bit-identity possible at all
    scale = qk.po2_scale(jnp.asarray(
        rng.uniform(0.01, 2.0, (300,)), jnp.float32))
    mine = jnp.asarray(rng.standard_normal((300, block)), jnp.float32)
    noise = qk.hop_noise(qk.schedule_key(0, 0, 0), 300, block)
    for label, nz in (("round-to-nearest", None), ("stochastic", noise)):
        a = qk.dequant_accum_requant(q, scale, mine, noise=nz,
                                     want_resid=True, impl="pallas")
        b = qk.dequant_accum_requant(q, scale, mine, noise=nz,
                                     want_resid=True, impl="jnp")
        for name, av, bv in zip(("q", "scale", "resid"), a, b):
            if not np.array_equal(np.asarray(av), np.asarray(bv)):
                print(f"FAIL: Pallas hop kernel vs jnp fallback diverge "
                      f"on {name} ({label})")
                return 1
    print("kernel: Pallas hop (interpret off-TPU) == jnp fallback "
          "(bitwise, incl. stochastic rounding + residual)")
    print("quant-smoke: OK")
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
