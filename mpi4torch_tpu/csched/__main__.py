"""``python -m mpi4torch_tpu.csched --smoke`` — the IR smoke lane.

Non-zero exit on ANY divergence.  Three legs (``make ir-smoke``):

1. **Registry guard** — ``analyze.registry.csched_problems``: every
   registered algorithm declares an IR program (or a native
   exemption), every step kind is covered by the lowering /
   interpreter / transposition / census dispatch tables.
2. **Re-expression matrix** — every registered allreduce algorithm,
   forward AND transposition-derived backward, deterministic and not:
   the IR lowering's StableHLO text equals the hand-written form's
   BIT FOR BIT on the 8-virtual-device mesh, and the interpreter
   equals the eager rendezvous fold bitwise; the q8 codec leg pins the
   per-step rewrite against the hand-composed fused pipeline the same
   way; the tree Bcast_/Reduce_ pair pins ``transpose(bcast) ==
   reduce`` at the text level.
3. **Synthesis verdict** — the census-ranked winner for the 8-device
   world beats the hand-written deterministic ring on wire bytes, its
   predicted HLO census matches ``analyze.parse_program`` of the
   actual lowering EXACTLY, and the search is deterministic.

``python -m mpi4torch_tpu.csched --tiers`` (``make tiers-smoke``) is
the multi-pod tier-stack lane (ISSUE 18): per nested factorization of
the 8-device world — (2,2,2), (4,2), (2,4), (8,) — the
bandwidth-weighted synthesis winner under skewed slow-outer
``tier_bandwidths`` beats the flat ``bidir`` baseline with the
outer-tier byte reduction confirmed by the per-tier census of the
ACTUAL lowering (``analyze.tier_wire_table``), every searched
composition (``TIER_PARITY_COVERED``/``TIER_CENSUS_COVERED``) holds
Mode A/B bitwise parity and a self-adjoint transposition, the 2-level
stack lowers text-identical to the historical hier forms, and
``obs.reconcile(..., tiers=)`` prices the measured Mode B per-tier
traffic EXACTLY.
"""

from __future__ import annotations

import json
import sys
from typing import Iterable, List

# Coverage literals of the ``--tiers`` lane (``make tiers-smoke``):
# which per-tier (algorithm x codec) compositions of the tier synthesis
# search space hold a Mode A/B bitwise parity cell and a per-tier
# census cell below.  ``analyze.registry.tier_program_problems``
# compares these against ``csched.TIER_COMPOSITIONS`` — a composition
# added to the search without lane coverage fails ``make tiers-smoke``
# AND ``make analyze-smoke`` structurally.
TIER_PARITY_COVERED = ("exact", "q8-slow")
TIER_CENSUS_COVERED = ("exact", "q8-slow")

# The nested factorizations the lane exercises on the 8-virtual-device
# world ((8,) is the degenerate single-tier stack — everything is top
# tier and the weighted census reduces to the flat one).
TIER_STACKS = ((2, 2, 2), (4, 2), (2, 4), (8,))


def _lower_text(fn, n: int, x, det: bool) -> str:
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from .. import config as _config
    from jax import shard_map
    from ..ops.spmd import SpmdContext

    mesh = Mesh(np.asarray(jax.devices()[:n]), ("w",))
    ctx = SpmdContext(axis_name="w", size=n)
    wrapped = shard_map(lambda v: fn(ctx, v), mesh=mesh, in_specs=P(),
                        out_specs=P(), check_vma=False)
    with _config.deterministic_mode(det):
        return jax.jit(wrapped).lower(x).as_text()


def _run_smoke() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .. import constants as C
    from .. import csched
    from ..analyze import parse_program
    from ..analyze.registry import csched_problems
    from ..compress import get_codec
    from ..compress import spmd as _cspmd
    from ..ops import eager as _eager
    from ..ops import spmd as _spmd

    failures: List[str] = []
    report = {"worlds": [8], "reexpression": {}, "codec": {},
              "bcast_reduce": {}, "synthesis": {}}

    def check(ok: bool, label: str):
        if not ok:
            failures.append(label)
        return bool(ok)

    n = 8
    x = jnp.arange(512, dtype=jnp.float32) / 3.0
    rng = np.random.default_rng(7)
    vals = [jnp.asarray(rng.standard_normal(257), jnp.float32)
            for _ in range(n)]

    # ---- leg 1: registry guard -------------------------------------
    problems = csched_problems()
    check(not problems, f"registry guard: {problems}")
    report["registry_problems"] = problems

    # ---- leg 2: re-expression matrix -------------------------------
    legacy = {
        "ring": lambda c, v, op, det:
            _spmd._ordered_fold_allreduce(c, v, op) if det
            else jax.lax.psum(v, c.axis_name),
        "rhd": lambda c, v, op, det: _spmd._rhd_allreduce_value(c, v, op),
        "tree": lambda c, v, op, det:
            _spmd._tree_allreduce_value(c, v, op),
        "hier": lambda c, v, op, det:
            _spmd._hier_allreduce_value(c, v, op),
        "bidir": lambda c, v, op, det:
            _spmd._bidir_allreduce_value(c, v, op),
        "torus": lambda c, v, op, det:
            _spmd._torus_allreduce_value(c, v, op),
    }
    legacy_bwd = dict(legacy)
    legacy_bwd["bidir"] = lambda c, v, op, det: (
        _spmd._ordered_fold_allreduce(c, v, op) if det
        else _spmd._bidir_allreduce_value(c, v, op, reverse=True))

    from .. import tune as _tune

    for algo in sorted(_tune.available_algorithms()):
        cell = {}
        for det in (False, True):
            t_legacy = _lower_text(
                lambda c, v: legacy[algo](c, v, C.MPI_SUM, det), n, x,
                det)
            t_ir = _lower_text(
                lambda c, v: _spmd._allreduce_fwd_value(
                    c, v, C.MPI_SUM, algo), n, x, det)
            cell[f"fwd_text_det={det}"] = check(
                t_legacy == t_ir, f"{algo} fwd text det={det}")
            tb_legacy = _lower_text(
                lambda c, v: legacy_bwd[algo](c, v, C.MPI_SUM, det), n,
                x, det)
            tb_ir = _lower_text(
                lambda c, v: _spmd._allreduce_bwd_value(c, v, algo), n,
                x, det)
            cell[f"bwd_text_det={det}"] = check(
                tb_legacy == tb_ir, f"{algo} bwd text det={det}")
        # interpreter == the eager rendezvous fold, bitwise
        prog = csched.allreduce_program(
            algo, n, C.MPI_SUM, deterministic=True, nelems=257,
            itemsize=4)
        _, fold = _eager._rendezvous_fold(n, algo)
        cell["interp_bitwise"] = check(
            bool(jnp.all(csched.interpret_allreduce(prog, C.MPI_SUM,
                                                    vals)
                         == fold(C.MPI_SUM, vals))),
            f"{algo} interpreter vs rendezvous fold")
        # transposition-derived vjp_census agreement
        cell["vjp_census"] = check(
            csched.declared_vjp_census(algo, n)
            == _tune.get_algorithm(algo).vjp_census,
            f"{algo} transposition vs declared vjp_census")
        report["reexpression"][algo] = cell

    # ---- leg 2b: the q8 codec rides per-step rewrites ---------------
    for cname in ("q8", "q8_ef_hop"):
        codec = get_codec(cname)
        for algo in ("ring", "bidir", "torus"):
            t_legacy = _lower_text(
                lambda c, v: _cspmd._fused_allreduce_value(
                    c, v, codec, algo, False), n, x, False)
            t_ir = _lower_text(
                lambda c, v: _cspmd._allreduce_value(c, v, codec, algo),
                n, x, False)
            report["codec"][f"{cname}/{algo}"] = check(
                t_legacy == t_ir, f"codec {cname}/{algo} text")
            base = codec.base()
            prog = csched.q8_allreduce_program(algo, n, cname,
                                               base.block)
            inner = _tune.resolve_hier_group(n) if algo == "torus" \
                else None
            ref = C.reduce_q8_hop(
                vals, block=base.block, algorithm=algo, inner=inner,
                stochastic=getattr(base, "stochastic", False),
                hop_ef=getattr(base, "hop_ef", False),
                ef_rounds=codec.ef_rounds)
            report["codec"][f"{cname}/{algo}/interp"] = check(
                bool(jnp.all(csched.interpret_allreduce(
                    prog, C.MPI_SUM, vals) == ref)),
                f"codec {cname}/{algo} interp vs reduce_q8_hop")

    # ---- leg 2c: tree Bcast_/Reduce_ transposition pair -------------
    t_bcast = _lower_text(
        lambda c, v: _spmd._tree_bcast_value(c, v, 1), n, x, False)
    t_bcast_ir = _lower_text(
        lambda c, v: csched.lower_value(
            csched.bcast_program("tree", n, 1, nbytes=x.size * 4),
            c, v), n, x, False)
    report["bcast_reduce"]["bcast_tree_text"] = check(
        t_bcast == t_bcast_ir, "tree Bcast_ text")
    t_reduce = _lower_text(
        lambda c, v: _spmd._tree_reduce_value(c, v, C.MPI_SUM, 1), n, x,
        False)
    t_red_transposed = _lower_text(
        lambda c, v: csched.lower_value(
            csched.transpose(csched.bcast_program(
                "tree", n, 1, nbytes=x.size * 4)), c, v), n, x, False)
    report["bcast_reduce"]["reduce_is_transposed_bcast"] = check(
        t_reduce == t_red_transposed,
        "transpose(tree Bcast_) == tree Reduce_")

    # ---- leg 3: synthesized-schedule census verdict -----------------
    res = csched.synthesize(n, 1 << 14, 4)
    res_again = csched.synthesize(n, 1 << 14, 4)
    synth_cell = {
        "winner": res["winner"],
        "chain": res["chain"],
        "wire_bytes_per_rank": res["census"]["wire_bytes_per_rank"],
        "ring_wire_bytes_per_rank":
            res["ring_census"]["wire_bytes_per_rank"],
        "synthesis_beats_ring": res["synthesis_beats_ring"],
    }
    check(res["synthesis_beats_ring"], "synthesis beats ring")
    synth_cell["deterministic"] = check(
        res["winner"] == res_again["winner"], "synthesis determinism")
    prog = res["program"]
    name = csched.install(prog)
    txt = _lower_text(
        lambda c, v: _spmd._allreduce_fwd_value(c, v, C.MPI_SUM, name),
        n, x, True)
    got = parse_program(txt).census()
    pred = csched.program_census(prog, x.size, 4)["hlo"]
    synth_cell["hlo_reconciles"] = check(
        all(got.get(k, 0) == v for k, v in pred.items()),
        f"synth census reconcile: parse={got} predicted={pred}")
    oracle = csched.interpret_allreduce(prog, C.MPI_SUM, vals)
    t_val = _lower_text(
        lambda c, v: _spmd._allreduce_fwd_value(c, v, C.MPI_SUM, name),
        n, x, True)
    synth_cell["lowerable"] = check(len(t_val) > 0, "synth lowerable")
    synth_cell["interp_finite"] = check(
        bool(jnp.all(jnp.isfinite(oracle))), "synth interp finite")
    report["synthesis"] = synth_cell

    report["failures"] = failures
    report["ok"] = not failures
    print(json.dumps(report, indent=1, sort_keys=True))
    return 0 if not failures else 1


def _mode_a_rows(name: str, n: int, vals, det: bool = True):
    """Execute an installed program Mode A over an ``n``-device mesh
    with per-rank values ``vals``; returns the per-rank result rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from .. import config as _config
    from .. import constants as C
    from jax import shard_map
    from ..ops.spmd import SpmdContext
    from ..ops import spmd as _spmd

    mesh = Mesh(np.asarray(jax.devices()[:n]), ("w",))
    ctx = SpmdContext(axis_name="w", size=n)
    stacked = jnp.stack(vals)
    wrapped = shard_map(
        lambda v: _spmd._allreduce_fwd_value(ctx, v[0], C.MPI_SUM,
                                             name)[None],
        mesh=mesh, in_specs=P("w"), out_specs=P("w"), check_vma=False)
    with _config.deterministic_mode(det):
        return jax.jit(wrapped)(stacked)


def _run_tiers() -> int:
    """``--tiers`` (``make tiers-smoke``): the multi-pod tier-stack
    verdict lane.  Non-zero exit on ANY divergence."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .. import config as _config
    from .. import constants as C
    from .. import csched
    from .. import analyze
    from ..analyze.registry import tier_program_problems
    from ..ops import spmd as _spmd

    failures: List[str] = []
    report = {"nranks": 8, "stacks": [list(s) for s in TIER_STACKS],
              "synthesis": {}, "parity": {}, "census": {},
              "two_level": {}, "reconcile": {}}

    def check(ok: bool, label: str):
        if not ok:
            failures.append(label)
        return bool(ok)

    n = 8
    x = jnp.arange(1024, dtype=jnp.float32)
    nbytes = x.size * 4
    # Integer-valued per-rank payloads: po2-scale block-q8 round-trips
    # integer grids exactly, so the q8-slow composition's Mode A/B
    # bitwise check is meaningful rather than comparing two rounding
    # paths.
    rng = np.random.default_rng(18)
    vals = [jnp.asarray(rng.integers(-40, 40, 257), jnp.float32)
            for _ in range(n)]

    # ---- leg 1: registry guard -------------------------------------
    problems = tier_program_problems()
    check(not problems, f"tier registry guard: {problems}")
    report["registry_problems"] = problems

    # ---- leg 2: weighted-census synthesis verdict -------------------
    # Skewed slow-outer bandwidths: the outermost tier (DCN) 20x under
    # the inner tiers (ICI) — the multi-pod shape the weighted census
    # exists for.
    for stack in TIER_STACKS:
        skew = tuple([1.0] * (len(stack) - 1) + [0.05]) \
            if len(stack) > 1 else (1.0,)
        res = csched.synthesize_tiers(n, nbytes, 4, tiers=stack,
                                      tier_bandwidths=skew)
        res2 = csched.synthesize_tiers(n, nbytes, 4, tiers=stack,
                                       tier_bandwidths=skew)
        key = "x".join(map(str, stack))
        cell = {
            "winner": res["winner"], "chain": res["chain"],
            "composition": res["composition"],
            "tier_wire": res["tier_wire"],
            "weighted_cost": res["weighted_cost"],
            "bidir_tier_wire": res["bidir_tier_wire"],
            "bidir_weighted_cost": res["bidir_weighted_cost"],
            "beats_bidir": res["beats_bidir"],
            "exact_beats_bidir": res["exact_beats_bidir"],
        }
        cell["deterministic"] = check(
            res["winner"] == res2["winner"],
            f"tiers {key}: synthesis determinism")
        if len(stack) > 1:
            cell["beats_bidir"] = check(
                res["beats_bidir"],
                f"tiers {key}: synthesized winner beats flat bidir on "
                "the weighted census")
            cell["outer_tier_reduced"] = check(
                res["tier_wire"][-1] < res["bidir_tier_wire"][-1],
                f"tiers {key}: outer-tier bytes reduced vs bidir "
                f"({res['tier_wire'][-1]} vs "
                f"{res['bidir_tier_wire'][-1]})")
            # Uniform bandwidths: the lossy variants must vanish (no
            # regression by construction) and the ranking degenerate to
            # the unweighted census.
            uni = csched.synthesize_tiers(n, nbytes, 4, tiers=stack)
            cell["uniform_all_exact"] = check(
                all(c["composition"] == "exact"
                    for c in uni["candidates"]),
                f"tiers {key}: uniform bandwidths admit lossy variants")
        report["synthesis"][key] = cell

        # ---- leg 3: per-tier census of the ACTUAL lowering ----------
        for label, prog in (("winner", res["program"]),
                            ("exact", res["exact_program"])):
            name = csched.install(prog)
            txt = _lower_text(
                lambda c, v: _spmd._allreduce_fwd_value(
                    c, v, C.MPI_SUM, name), n, x, True)
            got = analyze.tier_wire_table(txt, stack)
            pred = csched.program_tier_census(prog, x.size, 4, stack)
            report["census"][f"{key}/{label}"] = check(
                got == pred,
                f"tiers {key}/{label}: analyze tier table {got} != "
                f"program tier census {pred}")
            wc = analyze.weighted_wire_cost(txt, skew, tiers=stack)
            report["census"][f"{key}/{label}/weighted"] = check(
                wc == csched.weighted_cost(pred, skew),
                f"tiers {key}/{label}: weighted_wire_cost mismatch")

    # ---- leg 4: Mode A/B bitwise parity per composition -------------
    stack = (2, 2, 2)
    for comp in TIER_PARITY_COVERED:
        prog = csched.fold_program(n, stack, stack)
        if comp == "q8-slow":
            prog = csched.rewrite_fold_codec(prog, (len(stack) - 1,))
        name = csched.install(prog)
        rows = _mode_a_rows(name, n, vals)
        oracle = csched.interpret_allreduce(prog, C.MPI_SUM, vals)
        cell = {}
        cell["a_vs_b_bitwise"] = check(
            bool(jnp.all(rows[0] == oracle)),
            f"tiers parity {comp}: Mode A != Mode B bitwise")
        cell["ranks_agree"] = check(
            all(bool(jnp.all(rows[r] == rows[0])) for r in range(n)),
            f"tiers parity {comp}: ranks disagree")
        # The ONE transposition rule still derives the backward: the
        # transposed program lowers and censuses as the forward does
        # (allreduce(SUM) is self-adjoint).
        bwd = csched.transpose(prog)
        cell["vjp_self"] = check(
            csched.program_tier_census(bwd, x.size, 4, stack)
            == csched.program_tier_census(prog, x.size, 4, stack),
            f"tiers parity {comp}: transposed tier census differs")
        report["parity"][comp] = cell

    # ---- leg 5: 2-level tier stack == hier, text-identical ----------
    # (a) flat world: config.tier_stack=(2,4) must lower the 'hier'
    # schedule byte-identically to the pre-tier hier_group_size form.
    t_base = _lower_text(
        lambda c, v: _spmd._allreduce_fwd_value(c, v, C.MPI_SUM,
                                                "hier"), n, x, True)
    _config.set_tier_stack((2, 4))
    try:
        t_tiered = _lower_text(
            lambda c, v: _spmd._allreduce_fwd_value(c, v, C.MPI_SUM,
                                                    "hier"), n, x, True)
    finally:
        _config.set_tier_stack(None)
    report["two_level"]["flat_hier_text"] = check(
        t_base == t_tiered,
        "2-level tier_stack changes the flat hier lowering")
    # (b) mesh world: the 2-axis TierStackBackend vs HierMeshBackend.
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from ..ops.spmd import HierMeshBackend, TierStackBackend

    mesh2 = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                 ("g", "l"))

    def lower_backend(back):
        wrapped = shard_map(lambda v: back.allreduce(v, C.MPI_SUM),
                            mesh=mesh2, in_specs=P(), out_specs=P(),
                            check_vma=False)
        return jax.jit(wrapped).lower(x).as_text()

    report["two_level"]["mesh_text"] = check(
        lower_backend(TierStackBackend(("g", "l"), (2, 4)))
        == lower_backend(HierMeshBackend(("g", "l"), (2, 4))),
        "2-axis TierStackBackend lowers differently from "
        "HierMeshBackend")

    # ---- leg 6: obs.reconcile prices per-tier traffic EXACTLY -------
    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import obs

    stack = (2, 2, 2)
    res = csched.synthesize_tiers(n, nbytes, 4, tiers=stack,
                                  tier_bandwidths=(1.0, 1.0, 0.05))
    name = csched.install(res["program"])
    comm = mpi.COMM_WORLD

    with obs.trace() as t:
        def body(rank):
            return comm.Allreduce(x * (rank + 1), mpi.MPI_SUM,
                                  algorithm=name)
        mpi.run_ranks(body, n)
    lowered = _lower_text(
        lambda c, v: _spmd._allreduce_fwd_value(c, v, C.MPI_SUM, name),
        n, x, True)
    rep = obs.reconcile(t.events, lowered, dropped=t.dropped,
                        tiers=stack)
    report["reconcile"] = {
        "measured_tier_wire": rep["measured"].get("tier_wire"),
        "predicted_tier_wire": rep["predicted"].get("tier_wire"),
        "matches": rep["matches"],
        "ok": rep["ok"],
    }
    check(rep["ok"] and rep["matches"].get("tier_wire"),
          f"reconcile per-tier mismatch: measured "
          f"{rep['measured'].get('tier_wire')} vs predicted "
          f"{rep['predicted'].get('tier_wire')} "
          f"(matches={rep['matches']})")

    report["failures"] = failures
    report["ok"] = not failures
    print(json.dumps(report, indent=1, sort_keys=True))
    return 0 if not failures else 1


def _main(argv: Iterable[str]) -> int:
    argv = list(argv)
    if "--smoke" in argv:
        return _run_smoke()
    if "--tiers" in argv:
        return _run_tiers()
    print(__doc__)
    return 0


if __name__ == "__main__":
    from mpi4torch_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    sys.exit(_main(sys.argv[1:]))
