"""`python -m mpi4torch_tpu.serve --smoke` — the serve-smoke lane.

End-to-end check of the serving subsystem on whatever devices are
attached (the Makefile's ``serve-smoke`` target runs it on the
8-virtual-device CPU mesh):

1. **engine-vs-oracle bitwise** — the continuous-batching engine's
   tokens vs per-request ``generate()``, with admission/eviction churn
   (4 requests through 2 slots), under EVERY registered scheduling
   policy — the registry-sync guard: a policy added to
   ``serve.POLICIES`` without appearing in ``PARITY_POLICIES`` (and
   thus this matrix) fails the lane;
2. **scheduled-exposure census** — the lowered Mode A decode step with
   the overlap schedule censuses strictly < 1.0 exposed decode
   collectives (the blocking baseline censuses 1.0 by construction);
3. **latency-tier selection** — with a measured latency crossover in
   place, ``serve.latency_report`` picks a latency-optimal algorithm
   for the real decode chunk sizes AND the lowered program carries the
   resolved ``Allreduce_start.<algo>`` span with no bandwidth-tier
   schedule anywhere in the decode step;
4. **fault composition** — a ``rank_death`` injected mid-decode on the
   eager world raises an attributed ``RankFailedError``;
5. **paged bitwise under block churn** (ISSUE 17) — the paged engine
   (tight pool: fewer pages than dense-equivalent, so pages churn and
   cached pages evict) bitwise vs the oracle under every policy;
6. **prefix sharing lowers the shared prefill exactly once** — two
   requests sharing a system prompt: the ``prefill_tokens`` census
   counts the shared prefix ONCE, and the sharers' table rows hold the
   SAME page ids for the shared span;
7. **counter mirror** — every ``ServeStats`` counter (its one
   declaration, ``ServeStats._COUNTERS``) appears in
   ``obs.prometheus_text()`` as an ``mpi4torch_serve_*`` metric;
8. **no-retrace census** — the paged decode step lowers to IDENTICAL
   program text across two different block-table states (the table is
   an argument, not structure), with one row scatter per K and V per
   layer and, off the TPU where the read is the gather, a stable
   block-gather op count.

Exits non-zero on any divergence, so the lane is a real check, not a
demo.
"""

from __future__ import annotations

import sys

from ..utils.profiling import ServeStats

# The parity-covered policies: must equal serve.POLICIES (checked
# below) so scheduling policies can never ship without oracle-parity
# coverage — the registry-sync guard discipline of test_tune/
# test_overlap, applied to admission scheduling.
PARITY_POLICIES = ("fcfs", "shortest_first")

# The policies covered by the PAGED engine-vs-oracle matrix (cell 5
# below and tests/test_serve.py::TestPagedOracleParity): must equal
# serve.POLICIES — analyze.registry.serve_paging_problems drifts
# otherwise.
PAGED_PARITY_POLICIES = ("fcfs", "shortest_first")

# Every ServeStats counter is mirrored into the obs metrics surface as
# mpi4torch_serve_<name> (cell 7 asserts the exposition literally): the
# counters are declared once, in utils.profiling.ServeStats._COUNTERS.
MIRRORED_SERVE_COUNTERS = ServeStats._COUNTERS


def _smoke() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import serve
    from mpi4torch_tpu.models import transformer as T

    ndev = len(jax.devices())
    size = 4 if ndev >= 4 else (2 if ndev >= 2 else 1)
    print(f"serve-smoke: {ndev} device(s), platform "
          f"{jax.devices()[0].platform}, TP world ({size},)")

    cfg = T.TransformerConfig(vocab=61, d_model=32, n_heads=4,
                              n_layers=2, d_ff=64, max_seq=32)
    params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                dtype=jnp.float32)
    prompts = [np.array([1, 2, 3]), np.array([4, 5, 6, 7, 8]),
               np.array([9, 10]), np.array([11, 12, 13, 14])]
    budgets = [6, 4, 5, 3]

    def oracle(p, n):
        return np.asarray(T.generate(
            cfg, params, jnp.asarray(p, jnp.int32)[None, :], n,
            dtype=jnp.float32)[0])

    want = [oracle(p, n) for p, n in zip(prompts, budgets)]

    # 1. Registry-sync guard (the shared checker in
    # mpi4torch_tpu.analyze.registry; message unchanged) + the
    # engine-vs-oracle parity matrix.
    from mpi4torch_tpu.analyze.registry import serve_policy_problems

    sync = serve_policy_problems(PARITY_POLICIES)
    if sync:
        for p in sync:
            print(f"FAIL: {p}")
        return 1

    def check(results, label) -> bool:
        for i, w in enumerate(want):
            if not np.array_equal(np.asarray(results[i]), w):
                print(f"FAIL: {label}: request {i} tokens diverge from "
                      f"per-request generate()")
                return False
        return True

    for policy in sorted(serve.POLICIES):
        eng = serve.Engine(
            cfg, params,
            serve.ServeConfig(slots=2, policy=policy, overlap=True),
            spmd=True, nranks=size)
        for p, n in zip(prompts, budgets):
            eng.submit(p, max_new=n)
        if not check(eng.run(), f"Mode A ({size},) policy={policy}"):
            return 1
    print(f"engine: bitwise == per-request generate() on ({size},), "
          f"both policies, across slot churn "
          f"({len(prompts)} requests / 2 slots)")

    if size > 1:
        def fn(rank):
            e = serve.Engine(cfg, params,
                             serve.ServeConfig(slots=2, overlap=True))
            for p, n in zip(prompts, budgets):
                e.submit(p, max_new=n)
            return e.run()

        outs = mpi.run_ranks(fn, size, timeout=300.0)
        if not check(outs[0], f"Mode B ({size},)"):
            return 1
        print(f"engine: Mode B ({size},) rank threads bitwise == oracle")

    # 2. Scheduled-exposure census of the decode step.
    census = {}
    for name, ov in (("overlap", True), ("blocking", False)):
        eng = serve.Engine(cfg, params,
                           serve.ServeConfig(slots=2, overlap=ov),
                           spmd=True, nranks=size)
        eng.submit(prompts[0], max_new=3)
        eng.step()
        census[name] = mpi.overlap.scheduled_exposure(eng.lower_step())
    co, cb = census["overlap"], census["blocking"]
    print(f"scheduled exposure: overlap {co['exposed_fraction']} "
          f"({co['n_buckets']} buckets), blocking "
          f"{cb['exposed_fraction']} ({cb['n_buckets']} buckets)")
    if size > 1:
        if not (co["n_buckets"] and co["exposed_fraction"] < 1.0):
            print("FAIL: overlap decode schedule does not census "
                  "< 1.0 exposed")
            return 1
        if cb["exposed_fraction"] != 1.0:
            print("FAIL: blocking decode baseline should census 1.0")
            return 1

    # 3. Latency-tier selection on the real decode message sizes.
    prev = mpi.config.latency_crossover_bytes()
    mpi.config.set_latency_crossover_bytes(1 << 14)
    try:
        rep = serve.latency_report(cfg, serve.ServeConfig(slots=2),
                                   size, jnp.float32)
        print(f"latency tier: {rep['chunk_bytes']} B decode chunks "
              f"(cache bucket {rep['cache_bucket_bytes']}) -> "
              f"{rep['algorithm']}")
        if size > 1 and not rep["latency_tier"]:
            print(f"FAIL: decode selection {rep} did not land in the "
                  "latency tier under the measured crossover")
            return 1
        eng = serve.Engine(cfg, params,
                           serve.ServeConfig(slots=2, overlap=True),
                           spmd=True, nranks=size)
        eng.submit(prompts[0], max_new=3)
        eng.step()
        txt = eng.lower_step().as_text(debug_info=True)
        if size > 1:
            if f"Allreduce_start.{rep['algorithm']}" not in txt:
                print("FAIL: lowered decode step does not carry the "
                      f"resolved Allreduce_start.{rep['algorithm']} "
                      "span")
                return 1
            if ".bidir" in txt or ".torus" in txt:
                print("FAIL: a bandwidth-tier schedule leaked into the "
                      "decode step")
                return 1
            print(f"latency tier: lowered decode step carries "
                  f"Allreduce_start.{rep['algorithm']} spans, no "
                  "bandwidth-tier schedule")
        res = eng.run()
        if not np.array_equal(np.asarray(res[0]),
                              oracle(prompts[0], 3)):
            print("FAIL: latency-tier engine diverges from the oracle")
            return 1
    finally:
        mpi.config.set_latency_crossover_bytes(prev)

    # 4. Fault composition: rank death mid-decode, attributed.
    if ndev >= 2:
        from mpi4torch_tpu import resilience as rz

        def dying(rank):
            e = serve.Engine(cfg, params, serve.ServeConfig(slots=2))
            e.submit(prompts[0], max_new=4)
            return e.run()

        try:
            with rz.fault_scope([rz.FaultSpec(
                    "rank_death", rank=1, op="Allreduce",
                    index=2 * cfg.n_layers)]):
                mpi.run_ranks(dying, 2, timeout=20.0)
            print("FAIL: rank_death mid-decode did not raise")
            return 1
        except mpi.RankFailedError as e:
            if e.ranks != frozenset({1}):
                print(f"FAIL: RankFailedError misattributed: {e.ranks}")
                return 1
        print("faults: rank_death mid-decode -> RankFailedError(ranks="
              "{1}) on every survivor")

    # 5. Paged engine bitwise under BLOCK CHURN (ISSUE 17): a pool
    # smaller than dense-equivalent, so pages churn (and cached pages
    # evict) while 4 requests run through 2 slots — plus the paged
    # registry-sync guard.
    from mpi4torch_tpu.analyze.registry import serve_paging_problems

    sync = serve_paging_problems()
    if sync:
        for p in sync:
            print(f"FAIL: {p}")
        return 1

    for policy in sorted(serve.POLICIES):
        serve.reset_stats()
        eng = serve.Engine(
            cfg, params,
            serve.ServeConfig(slots=2, policy=policy, overlap=True,
                              block_size=4, num_blocks=6),
            spmd=True, nranks=size)
        for p, n in zip(prompts, budgets):
            eng.submit(p, max_new=n)
        if not check(eng.run(),
                     f"paged Mode A ({size},) policy={policy}"):
            return 1
    print(f"paged engine: bitwise == per-request generate() on "
          f"({size},), both policies, 6-page pool churn")

    # 6. Prefix sharing: the shared prefix prefills EXACTLY ONCE.
    serve.reset_stats()
    eng = serve.Engine(cfg, params,
                       serve.ServeConfig(slots=2, block_size=4),
                       spmd=True, nranks=size)
    sys_prompt = np.arange(1, 9)                 # 8 tokens = 2 pages
    pa = np.concatenate([sys_prompt, [20, 21]])
    pb = np.concatenate([sys_prompt, [22]])
    ra = eng.submit(pa, max_new=4)
    rb = eng.submit(pb, max_new=4)
    eng.step()                     # both admitted: tables are live NOW
    sa = [s for r, s in eng.slot_log if r == ra][0]
    sb = [s for r, s in eng.slot_log if r == rb][0]
    shared_pages = [int(b) for b in eng._table[sb][:2]]
    if [int(b) for b in eng._table[sa][:2]] != shared_pages \
            or min(shared_pages) < 0:
        print(f"FAIL: sharers do not reference the SAME prefix pages "
              f"({list(eng._table[sa][:2])} vs {shared_pages})")
        return 1
    res = eng.run()
    for rid, p in ((ra, pa), (rb, pb)):
        if not np.array_equal(np.asarray(res[rid]), oracle(p, 4)):
            print("FAIL: prefix-sharing engine diverges from oracle")
            return 1
    snap = eng.stats.snapshot()
    want_prefill = len(pa) + (len(pb) - len(sys_prompt))
    if snap["prefill_tokens"] != want_prefill:
        print(f"FAIL: shared prefix not prefilled exactly once: "
              f"{snap['prefill_tokens']} prefill tokens, expected "
              f"{want_prefill} (= {len(pa)} + {len(pb)} - "
              f"{len(sys_prompt)} shared)")
        return 1
    if snap["prefix_hits"] != 1:
        print(f"FAIL: expected exactly one prefix hit, got "
              f"{snap['prefix_hits']}")
        return 1
    print(f"prefix sharing: {len(sys_prompt)}-token system prompt "
          f"prefilled once ({snap['prefill_tokens']} prefill tokens "
          f"for 2 requests), pages {shared_pages} shared by both slots")

    # 7. Counter mirror: every pinned ServeStats counter surfaces as an
    # mpi4torch_serve_* metric in the Prometheus exposition.
    from mpi4torch_tpu import obs

    txt = obs.prometheus_text()
    missing = [c for c in MIRRORED_SERVE_COUNTERS
               if f"mpi4torch_serve_{c} " not in txt]
    if missing:
        print(f"FAIL: counters missing from prometheus_text(): "
              f"{missing}")
        return 1
    print(f"obs mirror: all {len(MIRRORED_SERVE_COUNTERS)} serve "
          "counters exposed as mpi4torch_serve_*")

    # 8. No-retrace census: the paged decode step lowers IDENTICALLY
    # across two different block-table states — the table is data.
    eng = serve.Engine(cfg, params,
                       serve.ServeConfig(slots=2, block_size=4,
                                         overlap=True),
                       spmd=True, nranks=size)
    eng.submit(prompts[0], max_new=6)
    eng.step()
    txt1 = eng.lower_step().as_text(debug_info=False)
    eng.submit(prompts[1], max_new=4)   # second slot maps fresh pages
    eng.step()
    txt2 = eng.lower_step().as_text(debug_info=False)
    if txt1 != txt2:
        print("FAIL: paged decode step retraces across table states")
        return 1
    n_scatter = txt1.count('"stablehlo.scatter"(')
    if n_scatter != 2 * cfg.n_layers:
        print(f"FAIL: paged decode step censuses {n_scatter} scatter "
              f"ops; expected {2 * cfg.n_layers} (one row write per K "
              "and V per layer)")
        return 1
    n_gather = txt1.count('"stablehlo.gather"')
    if n_gather < 2 * cfg.n_layers:
        print(f"FAIL: paged decode step censuses only {n_gather} "
              f"gather ops; expected >= {2 * cfg.n_layers} "
              "(one block gather per K and V per layer)")
        return 1
    res = eng.run()
    if not (np.array_equal(np.asarray(res[0]), oracle(prompts[0], 6))
            and np.array_equal(np.asarray(res[1]),
                               oracle(prompts[1], 4))):
        print("FAIL: no-retrace engine diverges from oracle")
        return 1
    print(f"no-retrace: paged decode step text identical across table "
          f"states ({n_scatter} scatter, {n_gather} gather ops "
          "censused)")

    print("serve-smoke: OK")
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
