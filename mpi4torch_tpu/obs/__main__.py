"""`python -m mpi4torch_tpu.obs --smoke` — the obs-smoke lane.

Four verdict families, every one exit-coded (the census discipline:
a claim either reproduces exactly or the lane fails):

1. **Static-vs-runtime reconciliation** — four representative
   schedules run traced under the Mode B runtime and joined against
   the ``analyze`` predictions of their Mode A lowerings, all EXACT
   (wire bytes AND per-kind collective counts): a plain ring
   allreduce, a fused q8 bucket pair, the (8,)->(2,4) reshard
   migration (the PR 8 pinned 98304-byte plan), and an overlap serve
   decode step (split-phase RS+AG pairs, scheduled exposure riding
   along).
2. **Flight recorder** — an injected ``FaultSpec(kind="rank_death")``
   mid-collective must produce a postmortem NAMING the dead rank, with
   every survivor's event tail ending on the same torn collective
   signature, and the JSON + human-table dump written.
3. **Off-path census** — with no tracer (and with a Mode B-only
   tracer) the Mode A lowering is bit-identical to an obs-less build
   (hook monkeypatched out structurally); a ``mode_a`` tracer prices
   exactly one host callback per collective entry.
4. **Metrics surfaces** — retry events and integrity violations land
   in the unified registry next to their historical access paths, the
   serve collector aggregates, and the Prometheus exposition renders.

``make obs-smoke`` runs this on the 8-virtual-device CPU harness.
"""

from __future__ import annotations

import sys


def _fail(failures: list, msg: str) -> None:
    failures.append(msg)
    print(f"FAIL: {msg}")


def _ok(msg: str) -> None:
    print(f"ok  : {msg}")


def _lower(fn, *args):
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import mpi4torch_tpu as mpi
    from jax import shard_map

    mesh = Mesh(np.asarray(jax.devices()), ("w",))
    cm = mpi.comm_from_mesh(mesh, "w")
    return jax.jit(shard_map(lambda *a: fn(cm, *a), mesh=mesh,
                             in_specs=P(), out_specs=P(),
                             check_vma=False)).lower(*args)


def _reconcile_case(failures, name, mode_b_body, nranks, lowered):
    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import obs

    with obs.trace() as t:
        mpi.run_ranks(mode_b_body, nranks)
    rep = obs.reconcile(t.events, lowered, dropped=t.dropped)
    m, p = rep["measured"], rep["predicted"]
    detail = (f"measured {m['wire_bytes']} B {m['counts']} == "
              f"predicted {p['wire_bytes']} B {p['counts']}")
    if rep["ok"]:
        _ok(f"reconcile[{name}]: {detail}")
    else:
        _fail(failures, f"reconcile[{name}]: {detail} "
                        f"(matches={rep['matches']}, consistent="
                        f"{m['per_rank_consistent']}, dropped="
                        f"{rep['dropped_events']})")
    return rep


def _smoke_reconcile(failures) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import COMM_WORLD as comm

    # 1a. plain ring allreduce, 8 ranks.
    x8 = jnp.arange(1024, dtype=jnp.float32)

    def plain(rank):
        return comm.Allreduce(x8 * (rank + 1), mpi.MPI_SUM,
                              algorithm="ring")

    _reconcile_case(
        failures, "ring-allreduce", plain, 8,
        _lower(lambda cm, a: cm.Allreduce(a, mpi.MPI_SUM,
                                          algorithm="ring"), x8))

    # 1b. fused q8 buckets (two buckets; the in-schedule int8+scale
    # pipeline priced through the equivalent lowering).
    def tree_of(rank):
        return {"a": jnp.linspace(-1, 1, 768,
                                  dtype=jnp.float32) * (rank + 1),
                "b": jnp.linspace(-2, 2, 512,
                                  dtype=jnp.float32) * (rank + 1)}

    BB = 2048

    def fused(rank):
        return comm.Allreduce_tree(tree_of(rank), mpi.MPI_SUM,
                                   compression="q8", bucket_bytes=BB)

    _reconcile_case(
        failures, "fused-q8-buckets", fused, 8,
        _lower(lambda cm, tr: cm.Allreduce_tree(
            tr, mpi.MPI_SUM, compression="q8", bucket_bytes=BB),
            tree_of(0)))

    # 1c. the (8,)->(2,4) checkpoint-migration reshard (the PR 8
    # census shape: planned wire 98304 B vs the 917504 B gather).
    from mpi4torch_tpu import reshard as rs

    fl = rs.layout((8,), 0, None)
    tl = rs.layout((2, 4), 0, 1)
    G = (1024, 256)
    shard_shape = fl.shard_shape(G)

    def migrate(rank):
        x = jnp.arange(int(np.prod(shard_shape)), dtype=jnp.float32
                       ).reshape(shard_shape) * (rank + 1)
        return comm.Reshard(x, fl, tl)

    rep = _reconcile_case(
        failures, "reshard-(8,)->(2,4)", migrate, 8,
        _lower(lambda cm, a: cm.Reshard(a, fl, tl),
               jnp.zeros(shard_shape, jnp.float32)))
    if rep["predicted"]["wire_bytes"] != 98304:
        _fail(failures,
              f"reshard predicted wire {rep['predicted']['wire_bytes']}"
              " != the recorded 98304 B plan")

    # 1d. overlap serve decode step: one traced Mode B engine step per
    # rank (isolated behind a barrier sentinel) vs the Mode A
    # engine.lower_step() census.
    from mpi4torch_tpu import serve
    from mpi4torch_tpu.models import transformer as T
    from mpi4torch_tpu.runtime import current_rank_context

    cfg = T.TransformerConfig(vocab=61, d_model=32, n_heads=4,
                              n_layers=2, d_ff=64, max_seq=32)
    params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                dtype=jnp.float32)
    prompts = [np.array([1, 2, 3]), np.array([4, 5, 6, 7, 8])]
    nranks = 4

    from mpi4torch_tpu import obs

    with obs.trace() as t:
        def body(rank):
            ctx = current_rank_context()
            eng = serve.Engine(cfg, params,
                               serve.ServeConfig(slots=2, overlap=True))
            for p in prompts:
                eng.submit(p, max_new=3)
            eng.step()                     # admission + prefill + decode
            ctx.world.barrier(ctx.rank)    # sentinel: next step isolated
            eng.step()
            return True
        mpi.run_ranks(body, nranks)

    decode = []
    for r in range(nranks):
        er = t.events_for(rank=r)
        cut = max(i for i, e in enumerate(er) if e.op == "Barrier")
        decode.extend(er[cut + 1:])

    eng_a = serve.Engine(cfg, params,
                         serve.ServeConfig(slots=2, overlap=True),
                         spmd=True, nranks=nranks)
    eng_a.submit(prompts[0], max_new=3)
    eng_a.step()
    rep = obs.reconcile(decode, eng_a.lower_step(), dropped=t.dropped)
    m, p = rep["measured"], rep["predicted"]
    detail = (f"measured {m['wire_bytes']} B {m['counts']} == "
              f"predicted {p['wire_bytes']} B {p['counts']}, "
              f"exposure {p['scheduled_exposure']}")
    if rep["ok"] and p["scheduled_exposure"] == 0.0:
        _ok(f"reconcile[serve-decode-step]: {detail}")
    else:
        _fail(failures, f"reconcile[serve-decode-step]: {detail} "
                        f"(matches={rep['matches']})")
    serve.reset_stats()


def _smoke_flight(failures, workdir) -> None:
    import jax.numpy as jnp

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import COMM_WORLD as comm, obs
    from mpi4torch_tpu.obs.flight import last_event_signature
    from mpi4torch_tpu.resilience import fault_scope

    nranks, dead = 4, 1
    spec = mpi.FaultSpec("rank_death", rank=dead, op="Allreduce", index=2)
    err = None
    with obs.trace(ring=16) as t:
        with fault_scope([spec]):
            def body(rank):
                x = jnp.arange(64, dtype=jnp.float32) * (rank + 1)
                for _ in range(4):
                    x = comm.Allreduce(x, mpi.MPI_SUM)
                return x
            try:
                mpi.run_ranks(body, nranks, timeout=2.0)
            except mpi.RankFailedError as e:
                err = e
    if err is None:
        return _fail(failures, "flight: injected rank_death was not "
                               "raised as RankFailedError")
    pm = t.last_postmortem()
    if pm is None:
        return _fail(failures, "flight: no postmortem captured")
    if pm["failed_ranks"] != [dead]:
        return _fail(failures, f"flight: postmortem names "
                               f"{pm['failed_ranks']}, not [{dead}]")
    dead_sig = last_event_signature(pm, dead)
    bad = [r for r in range(nranks)
           if last_event_signature(pm, r) != dead_sig]
    if dead_sig is None or bad:
        return _fail(failures,
                     f"flight: survivor tails inconsistent with the "
                     f"dead rank's last event (ranks {bad})")
    paths = obs.dump_postmortem(pm, workdir)
    text = obs.format_postmortem(pm)
    if f"rank(s): [{dead}]" not in text:
        return _fail(failures, "flight: human table does not name the "
                               "dead rank")
    _ok(f"flight: rank_death postmortem names rank {dead}; all "
        f"{nranks} tails end on the torn collective "
        f"{dead_sig}; dumped {paths['json']}")
    # The timeline export renders the same trace.
    import json
    import os

    tpath = obs.write_chrome_trace(
        os.path.join(workdir, "modeb_trace.json"), t.events)
    with open(tpath, encoding="utf-8") as f:
        n = len(json.load(f)["traceEvents"])
    _ok(f"export: chrome/Perfetto trace with {n} events at {tpath}")


def _smoke_offpath(failures) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import obs
    from jax import shard_map

    mesh = Mesh(np.asarray(jax.devices()), ("w",))
    cm = mpi.comm_from_mesh(mesh, "w")
    x = jnp.ones((1 << 12,), jnp.float32)

    def lowered(compression=False):
        return jax.jit(shard_map(
            lambda a: cm.Allreduce(a, mpi.MPI_SUM,
                                   compression=compression),
            mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False)).lower(x).as_text()

    text_off = lowered()
    text_off_q8 = lowered("q8")
    hook = obs.tracing.spmd_collective_event
    try:
        obs.tracing.spmd_collective_event = lambda v, where: v
        same = (lowered() == text_off and lowered("q8") == text_off_q8)
    finally:
        obs.tracing.spmd_collective_event = hook
    if not same:
        _fail(failures, "off-path: obs-disabled lowering differs from "
                        "the obs-less build")
    else:
        _ok("off-path: obs-disabled lowering bit-identical to the "
            "obs-less build (plain + q8)")

    with obs.trace():            # Mode B-only tracer: must not move A
        moved = lowered() != text_off
    if moved:
        _fail(failures, "off-path: a Mode B-only tracer moved the "
                        "Mode A lowering")
    else:
        _ok("off-path: Mode B-only tracer leaves the Mode A lowering "
            "untouched")

    with obs.trace(mode_a=True):
        delta = (lowered().count("stablehlo.custom_call")
                 - text_off.count("stablehlo.custom_call"))
    if delta != 1:
        _fail(failures, f"off-path: mode_a tracer priced {delta} "
                        "custom_calls per collective entry, expected 1")
    else:
        _ok("off-path: mode_a tracer prices exactly 1 host callback "
            "per collective entry")


def _smoke_metrics(failures) -> None:
    import jax.numpy as jnp

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import COMM_WORLD as comm, config, obs
    from mpi4torch_tpu.resilience import fault_scope, guards

    obs.reset_metrics()
    # Retry surfacing: a dropped p2p message recovered by retries must
    # land in BOTH the historical World.retry_events attribute and the
    # unified counter.
    spec = mpi.FaultSpec("drop_p2p", rank=0, op="p2p", index=0)
    retry_events = []
    config.set_comm_retries(4)
    config.set_comm_backoff(0.05)
    try:
        with obs.trace():
            def body(rank):
                from mpi4torch_tpu.runtime import current_rank_context
                ctx = current_rank_context()
                if rank == 0:
                    ctx.world.p2p_send(0, 1, 7, jnp.ones(4))
                if rank == 1:
                    got = ctx.world.p2p_recv(0, 1, 7)
                    retry_events.append(ctx.world.retry_events)
                    return got
                return None
            with fault_scope([spec]):
                mpi.run_ranks(body, 2, timeout=0.3)
    finally:
        config.set_comm_retries(0)
        config.set_comm_backoff(0.05)
    counters = obs.snapshot()["counters"]
    if not retry_events or retry_events[0] < 1:
        _fail(failures, "metrics: dropped p2p was not recovered via "
                        "retries (World.retry_events stayed 0)")
    elif counters.get("comm_retry_events_total", 0) < 1:
        _fail(failures, "metrics: comm_retry_events_total missing from "
                        f"the registry (counters={counters})")
    else:
        _ok(f"metrics: retry_events={retry_events[0]} mirrored as "
            f"comm_retry_events_total="
            f"{counters['comm_retry_events_total']}")

    # Integrity-violation surfacing next to the historical ledger.
    guards.clear_violations()
    config.set_comm_finite_guard("warn")
    try:
        import warnings

        def nan_body(rank):
            x = jnp.full(4, float("nan") if rank == 1 else 1.0)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                return comm.Allreduce(x, mpi.MPI_SUM)
        mpi.run_ranks(nan_body, 2)
    finally:
        config.set_comm_finite_guard("off")
    viol = guards.last_violation()
    counters = obs.snapshot()["counters"]
    if viol is None or counters.get("integrity_violations_total", 0) < 1:
        _fail(failures, "metrics: finite-guard violation not mirrored "
                        f"(ledger={viol}, counters={counters})")
    else:
        _ok("metrics: integrity violation in ledger AND "
            "integrity_violations_total="
            f"{counters['integrity_violations_total']}")
        guards.clear_violations()

    # Prometheus text renders the namespace.
    text = obs.prometheus_text()
    if "mpi4torch_comm_retry_events_total" not in text \
            or "mpi4torch_serve_" not in text:
        _fail(failures, "metrics: prometheus exposition missing "
                        "namespaced families")
    else:
        _ok("metrics: prometheus exposition carries the mpi4torch_* "
            "namespace (comm + serve families)")


def _smoke() -> int:
    import tempfile

    import jax

    print(f"obs-smoke: {len(jax.devices())} device(s), platform "
          f"{jax.devices()[0].platform}")
    failures: list = []
    _smoke_reconcile(failures)
    with tempfile.TemporaryDirectory() as d:
        _smoke_flight(failures, d)
    _smoke_offpath(failures)
    _smoke_metrics(failures)
    verdict = (f"FAIL — {len(failures)} problem(s)" if failures
               else "all verdicts exact")
    print(f"obs-smoke: {verdict}")
    return 1 if failures else 0


def main(argv) -> int:
    if "--smoke" in argv:
        return _smoke()
    print(__doc__)
    return 0


if __name__ == "__main__":
    from mpi4torch_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    sys.exit(main(sys.argv[1:]))
