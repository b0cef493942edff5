"""`python -m mpi4torch_tpu.overlap --smoke` — the overlap-smoke lane.

Exercises the split-phase scheduler AND the ZeRO prefetch end to end on
whatever devices are attached (the Makefile's ``overlap-smoke`` target
runs it on the 8-virtual-device CPU mesh):

1. a DP gradient-tree allreduce through the windowed split-phase
   scheduler, checked BITWISE against the blocking fused form;
2. a full ZeRO step (windowed reduce-scatter + double-buffered
   parameter all-gather prefetch) vs the blocking step, bitwise;
3. a wall-clock probe of both schedules with the exposed-comm fraction
   of each (informational on CPU — the synchronous host collective
   runtime cannot hide wire time).

Exits non-zero on any parity mismatch, so the lane is a real check,
not a demo.
"""

from __future__ import annotations

import sys
import time


def _smoke() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu.parallel import zero as Z

    comm = mpi.COMM_WORLD
    n = len(jax.devices())
    print(f"overlap-smoke: {n} device(s), platform "
          f"{jax.devices()[0].platform}")

    rng = np.random.default_rng(0)
    tree = {f"layer{i}": jnp.asarray(
        rng.standard_normal(2048).astype(np.float32)) for i in range(6)}

    def avg(ov):
        return mpi.run_spmd(lambda t: comm.Allreduce_tree(
            t, mpi.MPI_SUM, bucket_bytes=4096, overlap=ov, mean=True))

    blocking = avg(None)(tree)
    overlapped = avg(True)(tree)
    for k in tree:
        if not np.array_equal(np.asarray(blocking[k]),
                              np.asarray(overlapped[k])):
            print(f"FAIL: scheduler allreduce tree diverges on {k}")
            return 1
    print("scheduler: 6-leaf tree, windowed split-phase == blocking "
          "fused (bitwise)")

    params = {"w": jnp.asarray(
        rng.standard_normal((64, 48)).astype(np.float32)),
        "b": jnp.asarray(rng.standard_normal(97).astype(np.float32))}
    grads = jax.tree.map(lambda p: p * 0.01, params)

    class _Sgd:
        def init(self, p):
            return None

        def update(self, g, s, p):
            return jax.tree.map(lambda x: -0.1 * x, g), None

    opt = _Sgd()

    def zstep(ov):
        def f(g):
            with mpi.config.fusion_scope(4096):
                st = Z.zero_init(comm, opt, params)
                return Z.zero_step(comm, opt, params, g, st,
                                   overlap=ov)[0]
        return mpi.run_spmd(f)

    zb = zstep(None)(grads)
    zo = zstep(True)(grads)
    for k in params:
        if not np.array_equal(np.asarray(zb[k]), np.asarray(zo[k])):
            print(f"FAIL: ZeRO overlap step diverges on {k}")
            return 1
    print("zero: windowed reduce-scatter + prefetched all-gather == "
          "blocking step (bitwise)")

    def timed(fn, arg, iters=5):
        fn(arg)
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(arg))
            times.append(time.perf_counter() - t0)
        times.sort()
        return times[len(times) // 2]

    tb, to = timed(zstep(None), grads), timed(zstep(True), grads)
    print(f"zero step: blocking {tb * 1e3:.2f} ms, overlap "
          f"{to * 1e3:.2f} ms (speedup {tb / max(to, 1e-12):.2f}x; "
          "informational on CPU — synchronous collectives cannot hide "
          "wire time)")

    # The deterministic story: census both step schedules
    # (overlap.scheduled_exposure).
    from . import scheduled_exposure

    def lowered(ov):
        def f(g):
            with mpi.config.fusion_scope(4096):
                st = Z.zero_init(comm, opt, params)
                return Z.zero_step(comm, opt, params, g, st,
                                   overlap=ov)[0]
        return jax.jit(mpi.run_spmd(f)).lower(grads)

    cb = scheduled_exposure(lowered(None))
    co = scheduled_exposure(lowered(True))
    print(f"scheduled exposure: blocking {cb['exposed_fraction']} "
          f"({cb['n_buckets']} buckets), overlap {co['exposed_fraction']} "
          f"({co['n_buckets']} buckets)")
    if (n > 1 and cb["n_buckets"]
            and not (co["exposed_fraction"] < cb["exposed_fraction"])):
        print("FAIL: windowed schedule does not lower the scheduled "
              "exposed-comm fraction")
        return 1
    print("overlap-smoke: OK")
    return 0


def main(argv) -> int:
    if "--smoke" in argv or not argv:
        return _smoke()
    print(__doc__)
    return 2


if __name__ == "__main__":
    from mpi4torch_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    sys.exit(main(sys.argv[1:]))
