"""``python -m mpi4torch_tpu.transport --smoke`` — the transport-smoke
lane (``make transport-smoke``).

What it proves, exiting non-zero on ANY divergence:

* **registry sync** — every registered transport backend is in the
  tested set below (a backend merged without parity coverage is a
  standing problem, surfaced here and in ``analyze-smoke``);
* **bitwise parity** — plain / deterministic-mode / fused-bucket / q8
  / reshard traffic computes bit-identical results on the thread and
  process backends ((3,) worlds, plus the (8,)→(2,4) reshard);
* **SIGKILL attribution** — a ``rank_death`` matrix cell on the
  process backend (the kill is a real ``SIGKILL`` of a real worker)
  still ends in the attributed raise with its fired-fault ledger;
* **exact obs reconcile** — a traced process-backend run reconciles
  against the matching Mode A lowering EXACTLY (wire bytes and
  per-kind counts), i.e. child-process events ship to the parent
  aggregator without loss or distortion.
"""

from __future__ import annotations

import sys

#: The backends the parity matrix below (and tests/test_transport.py)
#: actually exercises.  analyze.registry.transport_problems() compares
#: this against the live registry — register a backend, test a backend.
TESTED_BACKENDS = ("thread", "process")


def _fail(failures: list, msg: str) -> None:
    failures.append(msg)
    print(f"FAIL: {msg}")


def _ok(msg: str) -> None:
    print(f"ok  : {msg}")


def _bitwise(failures, name, body, nranks) -> None:
    import jax
    import numpy as np

    import mpi4torch_tpu as mpi

    a = mpi.run_ranks(body, nranks, backend="thread")
    b = mpi.run_ranks(body, nranks, backend="process")
    fa, ta = jax.tree_util.tree_flatten(a)
    fb, tb = jax.tree_util.tree_flatten(b)
    if ta != tb or len(fa) != len(fb):
        _fail(failures, f"parity[{name}]: result STRUCTURE diverges")
        return
    for i, (x, y) in enumerate(zip(fa, fb)):
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype != y.dtype or x.shape != y.shape \
                or not np.array_equal(x, y, equal_nan=True):
            _fail(failures, f"parity[{name}]: leaf {i} diverges "
                            f"(thread {x.dtype}{x.shape} vs process "
                            f"{y.dtype}{y.shape})")
            return
    _ok(f"parity[{name}]: {len(fa)} leaves × {nranks} ranks bitwise "
        "identical across backends")


def _smoke_parity(failures) -> None:
    import jax.numpy as jnp
    import numpy as np

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import COMM_WORLD as comm
    from mpi4torch_tpu import reshard as rs

    def plain(rank):
        x = jnp.sin(jnp.arange(96, dtype=jnp.float32)) * (rank + 1)
        return comm.Allreduce(x, mpi.MPI_SUM)

    _bitwise(failures, "plain", plain, 3)

    def det(rank):
        x = jnp.sin(jnp.arange(96, dtype=jnp.float32)) * (rank + 1)
        with mpi.config.deterministic_mode(True):
            return comm.Allreduce(x, mpi.MPI_SUM)

    _bitwise(failures, "deterministic", det, 3)

    def fused(rank):
        tree = {"a": jnp.arange(24, dtype=jnp.float32) * (rank + 1),
                "b": jnp.ones(8, jnp.float32) * rank}
        return comm.Allreduce_tree(tree, mpi.MPI_SUM, bucket_bytes=64)

    _bitwise(failures, "fused", fused, 3)

    def q8(rank):
        x = jnp.linspace(-2.0, 2.0, 96, dtype=jnp.float32) * (rank + 1)
        return comm.Allreduce(x, mpi.MPI_SUM, compression="q8")

    _bitwise(failures, "q8", q8, 3)

    fl = rs.layout((8,), 0, None)
    tl = rs.layout((2, 4), 0, 1)
    shard_shape = fl.shard_shape((256, 64))

    def migrate(rank):
        x = jnp.arange(int(np.prod(shard_shape)), dtype=jnp.float32
                       ).reshape(shard_shape) * (rank + 1)
        return comm.Reshard(x, fl, tl)

    _bitwise(failures, "reshard-(8,)->(2,4)", migrate, 8)


def _smoke_sigkill(failures) -> None:
    from ..resilience.matrix import run_cell

    rec = run_cell("rank_death", "plain", nranks=3, backend="process")
    if rec["status"] == "ok" and "rank_death" in rec["fired"]:
        _ok(f"sigkill[rank_death×plain×process]: {rec['detail']} "
            f"(fired={rec['fired']})")
    else:
        _fail(failures, "sigkill[rank_death×plain×process]: "
                        f"{rec['detail']} (fired={rec['fired']})")


def _smoke_reconcile(failures) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import jax.numpy as jnp

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import COMM_WORLD as comm
    from mpi4torch_tpu import obs
    from jax import shard_map

    x8 = jnp.arange(1024, dtype=jnp.float32)

    def body(rank):
        return comm.Allreduce(x8 * (rank + 1), mpi.MPI_SUM,
                              algorithm="ring")

    mesh = Mesh(np.asarray(jax.devices()), ("w",))
    cm = mpi.comm_from_mesh(mesh, "w")
    lowered = jax.jit(shard_map(
        lambda a: cm.Allreduce(a, mpi.MPI_SUM, algorithm="ring"),
        mesh=mesh, in_specs=P(), out_specs=P(),
        check_vma=False)).lower(x8)

    with obs.trace() as t:
        mpi.run_ranks(body, 8, backend="process")
    rep = obs.reconcile(t.events, lowered, dropped=t.dropped)
    m, p = rep["measured"], rep["predicted"]
    detail = (f"measured {m['wire_bytes']} B {m['counts']} == "
              f"predicted {p['wire_bytes']} B {p['counts']}")
    if rep["ok"]:
        _ok(f"reconcile[process-wire ring-allreduce]: {detail}")
    else:
        _fail(failures, f"reconcile[process-wire ring-allreduce]: "
                        f"{detail} (matches={rep['matches']}, dropped="
                        f"{rep['dropped_events']})")


def _smoke() -> int:
    import jax

    from ..analyze.registry import transport_problems

    ndev = len(jax.devices())
    print(f"transport-smoke: {ndev} device(s), platform "
          f"{jax.devices()[0].platform}")

    failures: list = []
    for p in transport_problems():
        _fail(failures, f"[registry] {p}")
    if not failures:
        _ok(f"registry: TRANSPORTS == tested backends "
            f"{list(TESTED_BACKENDS)}")

    _smoke_parity(failures)
    _smoke_sigkill(failures)
    _smoke_reconcile(failures)

    from . import shutdown
    shutdown()

    if failures:
        print(f"\ntransport-smoke: {len(failures)} failure(s)")
        return 1
    print("\ntransport-smoke: all cells passed")
    return 0


def main(argv) -> int:
    if "--smoke" in argv:
        return _smoke()
    print(__doc__)
    return 2


if __name__ == "__main__":
    from mpi4torch_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    sys.exit(main(sys.argv[1:]))
