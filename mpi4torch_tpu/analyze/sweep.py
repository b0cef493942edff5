"""Registry-wide lint sweep: lower everything registered, lint it all.

The lints (:mod:`.lints`) only help if they run over the schedules the
registries can actually emit — all of them, not the handful a test
happened to lower.  This module enumerates, from the LIVE registries,

* every (algorithm × codec) Allreduce pair
  (``tune.available_algorithms()`` × the codecs declaring each
  algorithm, via the same ``codec_rides_algorithm`` predicate the
  facade enforces), forward AND ``value_and_grad`` backward, with the
  VJP-symmetry lint checking each algorithm's declared
  ``AlgorithmSpec.vjp_census`` transpose;
* the Bcast_/Reduce_ forms of the algorithms serving those collectives;
* every reshard strategy (``reshard.STRATEGIES``), each on a transition
  that exercises it, forward and adjoint — feeding the step-kind
  coverage leg of the reshard registry guard;
* the overlap schedules (windowed fused tree + the serve decode
  primitive ``overlap_split_allreduce``) — the split-phase lint's
  real-program coverage;
* the serve decode schedule (``Engine.lower_step``), overlap and
  blocking.

Every lowering runs the full structural lint set; a single violation
anywhere fails the sweep (``python -m mpi4torch_tpu.analyze --sweep``
exits non-zero — the ``make analyze-smoke`` lane).  Schedules a world
cannot serve (rhd on a non-power-of-two world, torus without a
factorization) are recorded as *skipped with the registry's own
reason*, never silently dropped.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from .accounting import scheduled_exposure
from .lints import check_vjp_symmetry, run_lints
from .parse import parse_program

__all__ = ["run_sweep", "sweep_worlds"]


def sweep_worlds(ndev: int) -> List[Tuple]:
    """The standard sweep worlds an ``ndev``-device harness can serve:
    the full flat world, the (3,) non-power-of-two world, the
    single-rank world, and the (2,4) two-axis mesh on 8 devices."""
    worlds: List[Tuple] = [(ndev,)]
    if ndev >= 3:
        worlds.append((3,))
    worlds.append((1,))
    if ndev == 8:
        worlds.append((2, 4))
    return worlds


def _flat_lowerer(nranks: int):
    """(lower, comm) over a fresh mesh of the first ``nranks``
    devices: ``lower(body, *args)`` -> debug-info StableHLO text of the
    shard_mapped ``body(comm, *args)``."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import mpi4torch_tpu as mpi
    from jax import shard_map

    mesh = Mesh(np.asarray(jax.devices()[:nranks]), ("w",))
    comm = mpi.comm_from_mesh(mesh, "w")

    def lower(body, *args):
        fn = shard_map(lambda *a: body(comm, *a), mesh=mesh,
                       in_specs=P(), out_specs=P(), check_vma=False)
        return jax.jit(fn).lower(*args).as_text(debug_info=True)

    return lower, comm


def _mesh2d_lowerer(shape: Tuple[int, int]):
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import mpi4torch_tpu as mpi
    from jax import shard_map

    a, b = shape
    mesh = Mesh(np.asarray(jax.devices()[:a * b]).reshape(a, b),
                ("outer", "inner"))
    comm = mpi.comm_from_mesh(mesh, ("outer", "inner"))

    def lower(body, *args):
        fn = shard_map(lambda *a_: body(comm, *a_), mesh=mesh,
                       in_specs=P(), out_specs=P(), check_vma=False)
        return jax.jit(fn).lower(*args).as_text(debug_info=True)

    return lower, comm


def _lint_case(records: List[dict], case: str, fwd_text: str,
               fwdbwd_text: Optional[str] = None,
               vjp_declaration=None, extra: Optional[dict] = None):
    """Run the structural lints (and, when a declaration is given, the
    VJP-symmetry lint) and append one sweep record."""
    fwd = parse_program(fwd_text)
    violations = run_lints(fwd)
    if fwdbwd_text is not None:
        bwd = parse_program(fwdbwd_text)
        violations += run_lints(bwd)
        if vjp_declaration is not None:
            violations += check_vjp_symmetry(
                fwd, bwd, vjp_declaration, context=case)
    rec = {"case": case, "skipped": None,
           "census": {k: v for k, v in fwd.census().items() if v},
           "violations": [str(v) for v in violations]}
    if extra:
        rec.update(extra)
    records.append(rec)


def _skip(records: List[dict], case: str, reason: str):
    records.append({"case": case, "skipped": reason, "census": {},
                    "violations": []})


def _sweep_allreduce_flat(records: List[dict], nranks: int,
                          nelem: int = 512):
    import jax
    import jax.numpy as jnp

    import mpi4torch_tpu as mpi
    from .. import tune
    from ..compress import available_codecs, codec_rides_algorithm, \
        get_codec

    lower, comm = _flat_lowerer(nranks)
    x = jnp.ones((nelem,), jnp.float32)

    for algo in tune.available_algorithms():
        spec = tune.get_algorithm(algo)
        why = spec.why_not(nranks)
        if why is not None:
            _skip(records, f"({nranks},) allreduce.{algo}", why)
            continue
        codecs = [None] + [
            c for c in available_codecs()
            if codec_rides_algorithm(get_codec(c), algo)]
        for codec in codecs:
            tag = f"({nranks},) allreduce.{algo}" + (
                f".{codec}" if codec else "")

            def body(c, v, algo=algo, codec=codec):
                return c.Allreduce(v, mpi.MPI_SUM, algorithm=algo,
                                   compression=codec or False)

            def loss(c, v, body=body):
                return jax.value_and_grad(
                    lambda u: jnp.sum(body(c, u)))(v)

            _lint_case(records, tag, lower(body, x), lower(loss, x),
                       vjp_declaration=spec.vjp_census)

    # The bcast/reduce forms of the algorithms that serve them: the
    # adjoint of Bcast_ is a Reduce_ (and vice versa) — a cross-op
    # transpose test_hlo censuses — so these legs run the structural
    # lints on the forward lowering.
    for collective, op in (("bcast", "Bcast_"), ("reduce", "Reduce_")):
        for algo in tune.available_algorithms():
            spec = tune.get_algorithm(algo)
            if spec.why_not(nranks, collective) is not None:
                continue

            def body(c, v, algo=algo, op=op):
                if op == "Bcast_":
                    return c.Bcast_(v, root=0, algorithm=algo)
                return c.Reduce_(v, mpi.MPI_SUM, root=0,
                                 algorithm=algo)

            _lint_case(records, f"({nranks},) {collective}.{algo}",
                       lower(body, x))


def _sweep_allreduce_2d(records: List[dict], shape: Tuple[int, int],
                        nelem: int = 512):
    import jax
    import jax.numpy as jnp

    import mpi4torch_tpu as mpi

    lower, comm = _mesh2d_lowerer(shape)
    x = jnp.ones((nelem,), jnp.float32)
    label = f"{shape}"

    # The 2-axis hier backend owns its algorithm resolution: its native
    # grouped schedule, plus the explicit hier/torus forms it can
    # lower; no codec pipeline (supports_compression=False).
    for algo in (None, "hier", "torus"):
        tag = f"{label} allreduce." + (algo or "native")

        def body(c, v, algo=algo):
            return c.Allreduce(v, mpi.MPI_SUM, algorithm=algo)

        def loss(c, v, body=body):
            return jax.value_and_grad(
                lambda u: jnp.sum(body(c, u)))(v)

        _lint_case(records, tag, lower(body, x), lower(loss, x),
                   vjp_declaration="self")


def _reshard_factors(n: int) -> Optional[Tuple[int, int]]:
    for a in range(2, n):
        if n % a == 0 and n // a > 1:
            return (a, n // a)
    return None


def _sweep_reshard(records: List[dict], nranks: int):
    """Every reshard strategy on a transition that exercises it;
    returns the step kinds the planned forward+adjoint programs
    covered (the registry guard's sweep-coverage leg)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mpi4torch_tpu as mpi
    from .. import reshard as rs

    lower, comm = _flat_lowerer(nranks)
    n = nranks
    factors = _reshard_factors(n)
    G = (4 * n, n)
    exercised: set = set()

    cases: List[Tuple[str, object, object]] = [
        ("local", rs.layout((n,), None, None), rs.layout((n,), 0, None)),
        ("gather", rs.layout((n,), None, None),
         rs.layout((n,), 0, None)),
    ]
    if factors is not None:
        a, b = factors
        cases += [
            ("alltoall", rs.layout((n,), 0, None),
             rs.layout((a, b), 0, 1)),
            ("rounds", rs.layout((n,), 0, None),
             rs.layout((a, b), 0, 1)),
            ("allgather", rs.layout((n,), 0, None),
             rs.layout((a, b), (0,), None)),
            ("permute", rs.layout((a, b), (0, 1), None),
             rs.layout((a, b), (1, 0), None)),
            ("gather", rs.layout((n,), 0, None),
             rs.layout((a, b), 0, 1)),
        ]
    ran = set()
    for strategy, fl, tl in cases:
        tag = f"({nranks},) reshard.{strategy}"
        if tag in ran:
            tag += ".migrate"
        ran.add(tag)
        plan = rs.plan_reshard(fl, tl, G, np.float32, strategy)
        exercised |= {s.kind for s in plan.steps}
        exercised |= {s.kind for s in plan.adjoint().steps}

        def body(c, v, fl=fl, tl=tl, strategy=strategy):
            return c.Reshard(v, fl, tl, strategy=strategy)

        def loss(c, v, body=body):
            return jax.value_and_grad(
                lambda u: jnp.sum(body(c, u)))(v)

        x = jnp.zeros(fl.shard_shape(G), jnp.float32)
        _lint_case(records, tag, lower(body, x), lower(loss, x))

    missing = sorted(set(rs.STRATEGIES)
                     - {c[0] for c in cases})
    for strategy in missing:
        _skip(records, f"({nranks},) reshard.{strategy}",
              f"needs a 2-level factorization; {n} has none")
    return exercised, factors is not None


def _sweep_overlap(records: List[dict], nranks: int):
    import jax.numpy as jnp

    import mpi4torch_tpu as mpi
    from ..overlap import overlap_split_allreduce

    lower, comm = _flat_lowerer(nranks)

    tree = {f"p{i}": jnp.ones((192 + 8 * i,), jnp.float32)
            for i in range(4)}

    def fused(c, t):
        return c.Allreduce_tree(t, mpi.MPI_SUM, bucket_bytes=1024,
                                overlap=2)

    txt = lower(fused, tree)
    _lint_case(records, f"({nranks},) overlap.allreduce_tree", txt,
               extra={"scheduled_exposure":
                      scheduled_exposure(txt)["exposed_fraction"]})

    def split(c, v):
        return overlap_split_allreduce(c, v, mpi.MPI_SUM, nsplits=3)

    txt = lower(split, jnp.ones((1536,), jnp.float32))
    _lint_case(records, f"({nranks},) overlap.split_allreduce", txt,
               extra={"scheduled_exposure":
                      scheduled_exposure(txt)["exposed_fraction"]})


def _sweep_serve(records: List[dict], nranks: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models import transformer as T
    from ..serve import Engine, ServeConfig

    ndev = len(jax.devices())
    size = min(nranks, 4 if ndev >= 4 else (2 if ndev >= 2 else 1))
    cfg = T.TransformerConfig(vocab=37, d_model=16, n_heads=4,
                              n_layers=1, d_ff=32, max_seq=16)
    params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                dtype=jnp.float32)
    for name, ov in (("overlap", True), ("blocking", False)):
        eng = Engine(cfg, params, ServeConfig(slots=2, overlap=ov),
                     spmd=True, nranks=size)
        eng.submit(np.array([1, 2, 3]), max_new=2)
        eng.step()
        txt = eng.lower_step().as_text(debug_info=True)
        _lint_case(
            records, f"({size},) serve.decode.{name}", txt,
            extra={"scheduled_exposure":
                   scheduled_exposure(txt)["exposed_fraction"]})


def run_sweep(world: Tuple[int, ...], include_serve: bool = True
              ) -> Dict:
    """Lint-sweep every registered schedule the ``world`` (a flat
    ``(n,)`` or two-axis ``(a, b)`` rank shape, served from the
    attached devices) can lower.  Returns ``{"world", "records",
    "n_cases", "n_skipped", "violations", "problems"}`` — ``problems``
    carries the standing registry-sync guards plus the reshard
    step-kind coverage of this sweep's own plans."""
    import jax

    from .registry import reshard_step_problems, standing_problems

    ndev = len(jax.devices())
    need = world[0] * (world[1] if len(world) > 1 else 1)
    if need > ndev:
        raise ValueError(
            f"world {world} needs {need} devices; {ndev} attached")

    records: List[dict] = []
    problems: List[str] = []
    if len(world) == 2:
        _sweep_allreduce_2d(records, world)
    else:
        n = world[0]
        _sweep_allreduce_flat(records, n)
        exercised, factorable = _sweep_reshard(records, n)
        problems += reshard_step_problems(
            exercised if factorable else None)
        if n >= 2:
            _sweep_overlap(records, n)
        if include_serve:
            _sweep_serve(records, n)
    problems += standing_problems()

    violations = [v for r in records for v in r["violations"]]
    return {
        "world": world,
        "records": records,
        "n_cases": sum(1 for r in records if r["skipped"] is None),
        "n_skipped": sum(1 for r in records if r["skipped"]),
        "violations": violations,
        "problems": problems,
    }
