"""mpi4torch_tpu.serve — continuous-batching inference serving
(ISSUE 10).

Coverage per the acceptance criteria:

* engine-vs-oracle TOKEN parity: the continuously-batched engine emits
  exactly the tokens of per-request ``models/transformer.generate`` —
  across admission/eviction churn, on (1,), (4,) and (2,4) worlds,
  Mode A (run_spmd) and Mode B (run_ranks), greedy AND sampled, under
  every registered scheduling policy (the matrix parametrizes over
  :data:`serve.POLICIES`, so a policy registered without parity
  coverage fails here — the registry-sync guard pins the known set);
* slot-table semantics: slot reuse after eviction, full-capacity
  rejection (``QueueFullError``), occupancy/eviction counters, and the
  NaN-poisoned free-slot inertness proof (poisoned rows never move live
  rows' logits by a single bit);
* the deterministic censuses: ``scheduled_exposure`` of the lowered
  decode step strictly < 1.0 with overlap on (blocking baseline 1.0),
  and the latency-tier evidence — ``latency_report`` + the resolved
  ``Allreduce_start.rhd`` span in the lowered program;
* Mode A/Mode B bitwise parity of ``decode_step_tp`` under
  ``deterministic_mode``;
* the ZeRO-3 → TP admission recipe (``admit_zero3`` bitwise equal to
  the gather-then-slice route, plus the serving-dtype override);
* fault composition: a ``rank_death`` mid-decode raises an attributed
  ``RankFailedError`` on every survivor.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4torch_tpu as mpi
from mpi4torch_tpu import serve, tune
from mpi4torch_tpu.models import transformer as T
from mpi4torch_tpu.serve import kv

CFG = T.TransformerConfig(vocab=37, d_model=16, n_heads=4, n_layers=2,
                          d_ff=32, max_seq=24)
CFG_GQA = dataclasses.replace(CFG, n_kv_heads=2)
CFG_ROPE = dataclasses.replace(CFG, rope=True)
CFG_SWIGLU = dataclasses.replace(CFG, ffn="swiglu")

PROMPTS = [np.array([1, 2, 3]), np.array([4, 5, 6, 7, 8]),
           np.array([9, 10]), np.array([11, 12, 13, 14])]
BUDGETS = [6, 4, 5, 3]

# Engine(**spmd): the eager engine of the local world, and the compiled
# step over two ranks.
SPMD = [{}, {"spmd": True, "nranks": 2}]
SPMD_IDS = ["eager", "spmd"]


def _params(cfg, seed=0):
    return T.init_transformer(jax.random.PRNGKey(seed), cfg,
                              dtype=jnp.float64)


def oracle_tokens(cfg, params, prompt, n_new, eos=None, temperature=0.0,
                  top_k=0, key=None):
    out = T.generate(cfg, params, jnp.asarray(prompt, jnp.int32)[None, :],
                     n_new, dtype=jnp.float64, temperature=temperature,
                     top_k=top_k, key=key)
    seq = np.asarray(out[0])
    if eos is not None:
        gen = seq[len(prompt):]
        hits = np.where(gen == eos)[0]
        if hits.size:
            seq = seq[:len(prompt) + hits[0] + 1]
    return seq


def drive(eng, keys=None):
    for i, (p, n) in enumerate(zip(PROMPTS, BUDGETS)):
        eng.submit(p, max_new=n,
                   key=None if keys is None else keys[i])
    return eng.run()


def assert_matches_oracle(cfg, params, results, eos=None,
                          temperature=0.0, top_k=0, keys=None):
    for i, (p, n) in enumerate(zip(PROMPTS, BUDGETS)):
        want = oracle_tokens(cfg, params, p, n, eos=eos,
                             temperature=temperature, top_k=top_k,
                             key=None if keys is None else keys[i])
        np.testing.assert_array_equal(np.asarray(results[i]), want)


@pytest.fixture(autouse=True)
def _serve_isolation(tmp_path, monkeypatch):
    monkeypatch.setenv("MPI4TORCH_TPU_TUNE_CACHE",
                       str(tmp_path / "tune_cache.json"))
    tune.clear()
    serve.reset_stats()
    yield
    tune.clear()
    serve.reset_stats()
    mpi.config.set_latency_crossover_bytes(None)
    mpi.config.set_serve_decode_buckets(
        mpi.config.DEFAULT_SERVE_DECODE_BUCKETS)


class TestEngineOracleParity:
    """Bitwise token parity vs per-request generate(), with slot churn
    (4 requests through 2 slots: queueing, eviction, slot reuse)."""

    @pytest.mark.parametrize("policy", sorted(serve.POLICIES))
    @pytest.mark.parametrize("cfg", [CFG, CFG_GQA, CFG_ROPE, CFG_SWIGLU],
                             ids=["mha", "gqa", "rope", "swiglu"])
    def test_local_churn_matrix(self, cfg, policy):
        params = _params(cfg)
        eng = serve.Engine(cfg, params,
                           serve.ServeConfig(slots=2, policy=policy))
        assert_matches_oracle(cfg, params, drive(eng))

    def test_spmd_world4_overlap(self):
        params = _params(CFG)
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=2, overlap=True),
                           spmd=True, nranks=4)
        assert_matches_oracle(CFG, params, drive(eng))

    def test_spmd_world4_blocking(self):
        params = _params(CFG)
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=2, overlap=False),
                           spmd=True, nranks=4)
        assert_matches_oracle(CFG, params, drive(eng))

    def test_spmd_mesh_2x4(self):
        params = _params(CFG)
        mesh = mpi.device_mesh({"dp": 2, "tp": 4})
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=2, overlap=True),
                           spmd=True, mesh=mesh, axis_name="tp")
        assert_matches_oracle(CFG, params, drive(eng))

    def test_ranks_world4_mode_b(self):
        params = _params(CFG)

        def fn(rank):
            eng = serve.Engine(CFG, params,
                               serve.ServeConfig(slots=2, overlap=True))
            return drive(eng)

        outs = mpi.run_ranks(fn, 4, timeout=120.0)
        # Every rank ran the identical host loop: identical results.
        for r in range(1, 4):
            for i in range(len(PROMPTS)):
                np.testing.assert_array_equal(outs[r][i], outs[0][i])
        assert_matches_oracle(CFG, params, outs[0])

    @pytest.mark.parametrize("spmd", SPMD, ids=SPMD_IDS)
    def test_sampled_parity_local(self, spmd):
        # Four requests through two slots: the slots are admitted in
        # different steps, so one step splits keys of different ages.
        params = _params(CFG)
        keys = [jax.random.PRNGKey(100 + i) for i in range(len(PROMPTS))]
        eng = serve.Engine(
            CFG, params,
            serve.ServeConfig(slots=2, temperature=0.9, top_k=7), **spmd)
        res = drive(eng, keys=keys)
        assert_matches_oracle(CFG, params, res, temperature=0.9,
                              top_k=7, keys=keys)

    def test_typed_key_is_served_as_its_raw_bits(self):
        params = _params(CFG)
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=2, temperature=0.9))
        eng.submit(PROMPTS[0], max_new=4, key=jax.random.key(7))
        np.testing.assert_array_equal(
            eng.run()[0],
            oracle_tokens(CFG, params, PROMPTS[0], 4, temperature=0.9,
                          key=jax.random.PRNGKey(7)))

    def test_eos_truncates_and_evicts_early(self):
        params = _params(CFG)
        # A naturally-emitted token as EOS: the engine must stop that
        # request right after it while the others run to budget.
        probe = oracle_tokens(CFG, params, PROMPTS[0], BUDGETS[0])
        eos = int(probe[len(PROMPTS[0]) + 1])     # its 2nd generated token
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=2, eos=eos))
        res = drive(eng)
        assert_matches_oracle(CFG, params, res, eos=eos)
        gen = probe[len(PROMPTS[0]):]
        first_hit = int(np.where(gen == eos)[0][0])
        assert len(res[0]) == len(PROMPTS[0]) + first_hit + 1
        assert res[0][-1] == eos
        assert len(res[0]) < len(PROMPTS[0]) + BUDGETS[0] + 1
        assert eng.stats.snapshot()["finished"] == len(PROMPTS)


class TestSelectRows:
    """``select_rows`` is ``Engine._select``'s rule applied to every
    row at once: tokens and key streams bit for bit."""

    SLOTS = 16

    def _table(self):
        rng = np.random.default_rng(3)
        table = rng.standard_normal((self.SLOTS, CFG.vocab))
        table[5, 30] = table[5, 11] = 7.5      # two maxima in one row
        table[9] = 0.25                        # and a row of nothing else
        return table

    @pytest.mark.parametrize("temperature,top_k",
                             [(0.0, 0), (0.9, 0), (0.9, 7)],
                             ids=["greedy", "t0.9", "t0.9-top7"])
    def test_bitwise_vs_sixteen_selects(self, temperature, top_k):
        table = self._table()
        keys = np.stack([np.asarray(jax.random.PRNGKey(40 + j))
                         for j in range(self.SLOTS)])
        eng = serve.Engine(CFG, _params(CFG), serve.ServeConfig(
            slots=1, temperature=temperature, top_k=top_k))
        want_tok, want_key = [], []
        for j in range(self.SLOTS):
            req = serve.Request(rid=j, prompt=PROMPTS[0], max_new=1,
                                key=jnp.asarray(keys[j]))
            want_tok.append(eng._select(req, table[j]))
            want_key.append(np.asarray(req.key))
        args = (jnp.asarray(table), jnp.asarray(keys))
        compiled = jax.jit(lambda t, k: serve.select_rows(
            t, k, temperature, top_k))
        for toks, new in (serve.select_rows(*args, temperature, top_k),
                          compiled(*args)):
            assert toks.dtype == jnp.int32 and toks.shape == (self.SLOTS,)
            np.testing.assert_array_equal(np.asarray(toks), want_tok)
            np.testing.assert_array_equal(np.asarray(new), want_key)
        if temperature == 0.0:
            # First maximum on ties; and the greedy engine's form,
            # which moves no key, chooses the same.
            assert (want_tok[5], want_tok[9]) == (11, 0)
            toks, new = serve.select_rows(args[0], None, 0.0, top_k)
            assert new is None
            np.testing.assert_array_equal(np.asarray(toks), want_tok)


ENGINE_KINDS = [pytest.param(paged, spmd, id=f"{p}-{i}")
                for paged, p in (({}, "dense"),
                                 ({"block_size": 4}, "paged"))
                for spmd, i in zip(SPMD, SPMD_IDS)]


@pytest.mark.parametrize("paged,spmd", ENGINE_KINDS)
class TestTokenHandOver:
    def test_instance_select_changes_every_emitted_token(self, paged,
                                                         spmd):
        """Every emitted token, the decode steps' too, passes through
        ``eng._select``: what the benchmark's broken-path control
        (``--break wrong_token``) rests on."""
        eng = serve.Engine(CFG, _params(CFG),
                           serve.ServeConfig(slots=2, **paged), **spmd)
        select, chosen = eng._select, []

        def wrong(req, choice):
            chosen.append(select(req, choice))
            return (chosen[-1] + 1) % CFG.vocab

        eng._select = wrong
        res = drive(eng)
        emitted = np.concatenate([np.asarray(res[i])[len(p):]
                                  for i, p in enumerate(PROMPTS)])
        assert len(chosen) == len(emitted) == sum(BUDGETS)
        # Requests interleave; compare as multisets of (chosen + 1).
        assert sorted(emitted) == sorted((np.asarray(chosen) + 1)
                                         % CFG.vocab)
        assert eng.stats.counters["decode_tokens"] \
            == sum(BUDGETS) - len(PROMPTS)
        assert eng.stats.counters["decode_select_syncs"] == 0

    def test_drained_key_is_the_oracles_stream(self, paged, spmd):
        """``req.key`` after n tokens is the oracle's key after n
        splits, so a drained sampled request re-admitted elsewhere
        continues bitwise."""
        from mpi4torch_tpu.elastic import replan as E

        params = _params(CFG)
        scfg = serve.ServeConfig(slots=2, temperature=0.9, top_k=7,
                                 **paged)
        keys = [jax.random.PRNGKey(100 + i) for i in range(2)]
        eng = serve.Engine(CFG, params, scfg, **spmd)
        for i in range(2):
            eng.submit(PROMPTS[i], max_new=6, key=keys[i])
        eng.step(); eng.step(); eng.step()
        tickets, _ = E.drain_tickets(eng)
        assert [len(t.emitted) for t in tickets] == [4, 4]
        for t, key in zip(tickets, keys):
            for _ in t.emitted:
                key = jax.random.split(key)[0]
            np.testing.assert_array_equal(np.asarray(t.key),
                                          np.asarray(key))
        eng2 = serve.Engine(CFG, params, scfg, **spmd)
        E.readmit(eng2, tickets)
        stitched = E.stitched_results(eng2.run(), tickets)
        for i in range(2):
            np.testing.assert_array_equal(
                stitched[i],
                oracle_tokens(CFG, params, PROMPTS[i], 6,
                              temperature=0.9, top_k=7, key=keys[i]))


class TestSlotTable:
    def test_slot_reuse_after_eviction(self):
        params = _params(CFG)
        eng = serve.Engine(CFG, params, serve.ServeConfig(slots=1))
        eng.submit(PROMPTS[0], max_new=2)
        eng.submit(PROMPTS[1], max_new=2)
        eng.run()
        # One slot, two requests: the second reused slot 0.
        assert eng.slot_log == [(0, 0), (1, 0)]
        assert eng.stats.snapshot()["evicted"] == 2

    def test_full_capacity_rejection(self):
        params = _params(CFG)
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=1, queue_limit=1))
        eng.submit(PROMPTS[0], max_new=3)
        eng.step()                       # occupies the single slot
        eng.submit(PROMPTS[1], max_new=3)   # waits in the queue
        with pytest.raises(serve.QueueFullError, match="queue full"):
            eng.submit(PROMPTS[2], max_new=3)
        assert eng.stats.snapshot()["rejected"] == 1
        # Draining frees capacity again.
        eng.run()
        assert eng.submit(PROMPTS[2], max_new=3) is not None

    def test_queue_bounded_before_first_step(self):
        """queue_limit must bound the waiting queue even while slots
        are still free (pre-step burst): capacity = free slots +
        queue_limit, nothing beyond it."""
        params = _params(CFG)
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=1, queue_limit=1))
        eng.submit(PROMPTS[0], max_new=2)    # absorbed by the free slot
        eng.submit(PROMPTS[1], max_new=2)    # the one queued-waiter
        with pytest.raises(serve.QueueFullError):
            eng.submit(PROMPTS[2], max_new=2)
        # Both accepted requests still serve to completion.
        res = eng.run()
        assert set(res) == {0, 1}

    def test_finite_guard_composes_with_poisoned_free_slots(self):
        """config.comm_finite_guard='raise' (the PR 7 integrity knob)
        must not false-positive on a partially-occupied engine: free
        slots' poisoned rows are masked out of every collective payload
        before it reaches the wire, and live tokens are unchanged."""
        params = _params(CFG)
        want = oracle_tokens(CFG, params, PROMPTS[0], 4)
        mpi.config.set_comm_finite_guard("raise")
        try:
            def fn(rank):
                eng = serve.Engine(CFG, params,
                                   serve.ServeConfig(slots=3))
                eng.submit(PROMPTS[0], max_new=4)   # 2 slots stay free
                return eng.run()

            outs = mpi.run_ranks(fn, 2, timeout=60.0)
        finally:
            mpi.config.set_comm_finite_guard("off")
        np.testing.assert_array_equal(outs[0][0], want)

    def test_admission_finish_reports_through_step_events(self):
        """A request that finishes at admission (max_new=1, or first
        token == eos) must surface through step()'s emitted/finished
        events like any decode-finished request."""
        params = _params(CFG)
        eng = serve.Engine(CFG, params, serve.ServeConfig(slots=2))
        eng.submit(PROMPTS[0], max_new=1)
        ev = eng.step()
        assert ev["admitted"] == [0] and ev["finished"] == [0]
        assert len(ev["emitted"][0]) == 1
        np.testing.assert_array_equal(
            eng.results()[0], oracle_tokens(CFG, params, PROMPTS[0], 1))
        # A longer request emits TWO tokens on its admission step:
        # the prefill first-token plus its first decode token.
        rid = eng.submit(PROMPTS[1], max_new=3)
        ev = eng.step()
        assert len(ev["emitted"][rid]) == 2

    def test_duplicate_rid_rejected(self):
        params = _params(CFG)
        eng = serve.Engine(CFG, params, serve.ServeConfig(slots=2))
        eng.submit(PROMPTS[0], rid="x", max_new=2)
        with pytest.raises(ValueError, match="already in use"):
            eng.submit(PROMPTS[1], rid="x", max_new=2)
        eng.run()
        # Still taken after finishing — results()['x'] must stay
        # unambiguous for the engine's lifetime.
        with pytest.raises(ValueError, match="already in use"):
            eng.submit(PROMPTS[1], rid="x", max_new=2)

    def test_pop_results_releases_memory_and_rids(self):
        """The steady-state serving API: pop finished results so a
        long-lived engine does not grow with total traffic; a popped
        rid becomes reusable."""
        params = _params(CFG)
        eng = serve.Engine(CFG, params, serve.ServeConfig(slots=2))
        eng.submit(PROMPTS[0], rid="x", max_new=2)
        eng.run()
        popped = eng.pop_results()
        np.testing.assert_array_equal(
            popped["x"], oracle_tokens(CFG, params, PROMPTS[0], 2))
        assert eng.results() == {}
        # rid released: a second life for "x" serves normally.
        eng.submit(PROMPTS[1], rid="x", max_new=2)
        eng.run()
        np.testing.assert_array_equal(
            eng.pop_results()["x"],
            oracle_tokens(CFG, params, PROMPTS[1], 2))

    def test_stats_registry_drops_collected_engines(self):
        import gc

        params = _params(CFG)
        eng = serve.Engine(CFG, params, serve.ServeConfig(slots=1))
        eng.submit(PROMPTS[0], max_new=2)
        eng.run()
        assert serve.stats()["n_engines"] == 1
        del eng
        gc.collect()
        snap = serve.stats()
        assert snap["n_engines"] == 0 and snap["finished"] == 0

    def test_occupancy_counters(self):
        params = _params(CFG)
        eng = serve.Engine(CFG, params, serve.ServeConfig(slots=4))
        eng.submit(PROMPTS[0], max_new=3)
        eng.run()
        snap = eng.stats.snapshot()
        assert snap["steps"] == 2            # budget 3 = prefill + 2 decodes
        assert snap["occupancy"] == 0.25     # 1 of 4 slots live
        assert snap["decode_tokens"] == 2
        span = eng.stats.spans[0]
        assert span["submitted"] <= span["admitted"] \
            <= span["first_token"] <= span["finished"]

    def test_poisoned_free_slots_are_inert(self):
        """NaN-poisoned rows must not move a live row's logits by one
        bit (all per-slot compute is row-local; collectives reduce over
        ranks, not slots)."""
        params = _params(CFG)
        comm = mpi.COMM_WORLD
        shards = kv.shard_params_tp(CFG, params, comm)
        tokens = jnp.asarray([5, 0], jnp.int32)
        pos = jnp.asarray([2, 0], jnp.int32)

        clean = kv.init_kv_cache_tp(CFG, 2, 1, jnp.float64)
        poisoned = jax.tree.map(lambda a: a.at[1].set(jnp.nan), clean)
        l_clean, _ = kv.decode_step_tp(CFG, shards, clean, tokens, pos,
                                       comm)
        l_pois, _ = kv.decode_step_tp(CFG, shards, poisoned, tokens, pos,
                                      comm)
        np.testing.assert_array_equal(np.asarray(l_clean[0]),
                                      np.asarray(l_pois[0]))
        assert np.all(np.isfinite(np.asarray(l_pois[0])))

    def test_submit_validation(self):
        params = _params(CFG)
        eng = serve.Engine(CFG, params, serve.ServeConfig(slots=1))
        with pytest.raises(ValueError, match="exceeds max_seq"):
            eng.submit(np.arange(20), max_new=10)
        with pytest.raises(ValueError, match="non-empty 1-d"):
            eng.submit(np.zeros((2, 2), np.int32))
        with pytest.raises(ValueError, match="requires a PRNG"):
            serve.Engine(CFG, params,
                         serve.ServeConfig(slots=1, temperature=0.5)) \
                .submit(PROMPTS[0])

    def test_config_validation(self):
        with pytest.raises(ValueError, match="unknown scheduling"):
            serve.ServeConfig(policy="round_robin")
        with pytest.raises(ValueError, match="slots"):
            serve.ServeConfig(slots=0)
        params = _params(CFG)
        with pytest.raises(mpi.CommError, match="n_heads"):
            serve.Engine(CFG, params, serve.ServeConfig(slots=1),
                         spmd=True, nranks=3)
        moe = dataclasses.replace(CFG, n_experts=2, capacity=8)
        with pytest.raises(mpi.CommError, match="MoE"):
            serve.Engine(moe, _params(moe), serve.ServeConfig(slots=1))


class TestPolicies:
    def test_registry_sync_guard(self):
        """Every registered policy is covered by the parity matrix
        (which parametrizes over serve.POLICIES); pinning the known set
        makes registering a policy without extending coverage a loud CI
        failure rather than a silent gap.  One checker
        (analyze.registry.serve_policy_problems) shared with the
        serve-smoke lane."""
        from mpi4torch_tpu.analyze.registry import serve_policy_problems

        assert serve_policy_problems(("fcfs", "shortest_first")) == []

    def test_shortest_first_orders_admissions(self):
        params = _params(CFG)
        eng = serve.Engine(
            CFG, params,
            serve.ServeConfig(slots=1, policy="shortest_first"))
        eng.submit(PROMPTS[1], max_new=2)   # len 5
        eng.submit(PROMPTS[2], max_new=2)   # len 2 — admitted first
        eng.run()
        assert [rid for rid, _ in eng.slot_log] == [1, 0]


class TestCensusAndLatencyTier:
    def test_scheduled_exposure_overlap_vs_blocking(self):
        params = _params(CFG)
        seen = {}
        for name, ov in (("overlap", True), ("blocking", False)):
            eng = serve.Engine(CFG, params,
                               serve.ServeConfig(slots=2, overlap=ov),
                               spmd=True, nranks=4)
            eng.submit(PROMPTS[0], max_new=3)
            eng.step()
            seen[name] = mpi.overlap.scheduled_exposure(eng.lower_step())
        k = mpi.config.serve_decode_buckets()
        assert seen["overlap"]["n_buckets"] == 2 * CFG.n_layers * k
        assert seen["overlap"]["exposed_fraction"] < 1.0
        assert seen["blocking"]["exposed_fraction"] == 1.0

    def test_latency_tier_selection_and_span(self):

        params = _params(CFG)
        mpi.config.set_latency_crossover_bytes(1 << 14)
        rep = serve.latency_report(CFG, serve.ServeConfig(slots=2), 4,
                                   jnp.float64)
        assert rep["latency_tier"] and rep["algorithm"] == "rhd"
        assert rep["chunk_bytes"] <= rep["latency_crossover_bytes"]

        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=2, overlap=True),
                           spmd=True, nranks=4)
        eng.submit(PROMPTS[0], max_new=3)
        eng.step()
        txt = eng.lower_step().as_text(debug_info=True)
        # Deterministic evidence off the program itself: the resolved
        # split-phase scope carries the latency algorithm, and no
        # bandwidth-tier schedule appears anywhere in the decode step.
        assert "Allreduce_start.rhd" in txt
        assert ".bidir" not in txt and ".torus" not in txt
        # Parity is schedule-independent.
        res = eng.run()
        np.testing.assert_array_equal(
            res[0], oracle_tokens(CFG, params, PROMPTS[0], 3))

    def test_degraded_scope_algorithm_not_claimed_in_span(self):
        """A scope-default hier whose group rule fails for this
        communicator degrades to ring inside the backend — the lowered
        split-phase scope must NOT claim the schedule that never ran
        (the census reads those spans as evidence)."""
        import jax as _jax

        comm = mpi.COMM_WORLD
        mpi.config.set_hier_group_size(5)    # does not divide 4
        try:
            with mpi.config.algorithm_scope("hier"):
                def body(x):
                    return comm.Wait(comm.Allreduce_start(x, mpi.MPI_SUM))
                lowered = _jax.jit(mpi.run_spmd(body, nranks=4)).lower(
                    jnp.ones(64, jnp.float32))
            txt = lowered.as_text(debug_info=True)
            assert "Allreduce_start.hier" not in txt
            assert "Allreduce_start" in txt
        finally:
            mpi.config.set_hier_group_size(None)

    @pytest.mark.parametrize("paged", [{}, {"block_size": 4}],
                             ids=["dense", "paged"])
    @pytest.mark.parametrize("temperature", [0.0, 0.9],
                             ids=["greedy", "sampled"])
    def test_lowered_step_hands_back_tokens_not_the_table(self, paged,
                                                          temperature):
        """The compiled step's results are ``(slots,)`` tokens with
        nothing counted behind them, the slot state it was given,
        advanced (the keys in it when sampling), and the cache: nothing
        of the logits table's shape; choosing and advancing add no
        collective to the step that ends in the logits."""
        from mpi4torch_tpu import analyze
        from mpi4torch_tpu.ops.spmd import run_spmd

        slots, size = 3, 4
        eng = serve.Engine(
            CFG, _params(CFG),
            serve.ServeConfig(slots=slots, temperature=temperature,
                              **paged), spmd=True, nranks=size)
        eng.submit(PROMPTS[0], max_new=3, key=jax.random.PRNGKey(1))
        eng.step()
        lowered = eng.lower_step()
        toks, state, cache = lowered.out_info
        # no expert layer, nothing counted behind the tokens
        assert (toks.shape, toks.dtype) == ((size, slots), jnp.int32)
        # the table rides for every block_size: one cache manager
        assert set(state) == {"tokens", "pos", "live", "table"} \
            | ({"keys"} if temperature else set())
        # the state goes into the next step as it came out of this one
        assert jax.tree.map(lambda a: (a.shape, a.dtype), state) \
            == jax.tree.map(lambda a: (a.shape, a.dtype), eng._state)
        if temperature:
            assert (state["keys"].shape, state["keys"].dtype) \
                == ((size, slots, 2), jnp.uint32)
        assert [leaf.shape for leaf in jax.tree.leaves(cache)] \
            == [leaf.shape for leaf in jax.tree.leaves(eng._cache)]
        for leaf in jax.tree.leaves(lowered.out_info):
            assert leaf.shape[-2:] != (slots, CFG.vocab)

        def logits_step(shards, cache, state):
            state = eng._rank_slice(state)
            table = (state["table"],) if paged else ()
            decode = kv.decode_step_paged if paged else kv.decode_step_tp
            return decode(CFG, eng._rank_slice(shards),
                          eng._rank_slice(cache), *table,
                          state["tokens"], state["pos"],
                          mpi.COMM_WORLD, overlap=eng.serve_cfg.overlap,
                          active=state["live"])

        parent = jax.jit(run_spmd(logits_step, nranks=size)).lower(
            eng._shards, eng._cache, eng._step_inputs())
        assert parent.out_info[0].shape == (size, slots, CFG.vocab)
        census = analyze.parse_program(lowered).census()
        assert census == analyze.parse_program(parent).census()
        assert sum(census.values()) > 0

    def test_decode_message_bytes(self):
        scfg = serve.ServeConfig(slots=2)
        assert serve.decode_message_bytes(CFG, scfg, jnp.float64) \
            == 2 * CFG.d_model * 8


class TestCrossModeBitwise:
    def test_decode_step_tp_det_mode_a_vs_b(self):
        params = _params(CFG)
        tokens = jnp.asarray([3, 5, 7], jnp.int32)
        pos = jnp.asarray([0, 1, 2], jnp.int32)
        with mpi.config.deterministic_mode():
            def step_a(cache, t, p):
                comm = mpi.COMM_WORLD
                sh = kv.shard_params_tp(CFG, params, comm)
                rank = jnp.asarray(comm.rank)
                local = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, rank, 0, keepdims=False), cache)
                return kv.decode_step_tp(CFG, sh, local, t, p, comm,
                                         overlap=True)[0]

            cache0 = kv.init_kv_cache_tp(CFG, 3, 4, jnp.float64)
            stacked = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (4,) + a.shape),
                cache0)
            l_a = mpi.run_spmd(step_a, nranks=4)(stacked, tokens, pos)

            def rank_fn(rank):
                comm = mpi.COMM_WORLD
                sh = kv.shard_params_tp(CFG, params, comm)
                local = kv.init_kv_cache_tp(CFG, 3, 4, jnp.float64)
                return kv.decode_step_tp(CFG, sh, local, tokens, pos,
                                         comm, overlap=True)[0]

            outs = mpi.run_ranks(rank_fn, 4, timeout=60.0)
        for r in range(4):
            np.testing.assert_array_equal(np.asarray(l_a[r]),
                                          np.asarray(outs[r]))


class TestOneServingBlock:
    @pytest.mark.parametrize("entry", [
        "prefill_tp", "prefill_chunk_tp", "decode_step_tp",
        "decode_step_paged"])
    def test_entry_points_refuse_a_recurrent_mixer(self, entry):
        # Called directly (validate_tp is the engine's gate too), each
        # serving program answers a layer the walk does not know, a KDA
        # mixer, with validate_tp's CommError, by name, before it reads
        # a block.  (Latent attention and the expert share it serves:
        # tests/test_openpangu_moe.py.)
        kda = T.LayerSpec(T.KDA(n_heads=2, head_dim=8))
        cfg = dataclasses.replace(CFG_ROPE, layers=(kda,) * 2)
        params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                    dtype=jnp.float32)
        tokens = jnp.asarray([3, 5], jnp.int32)
        pos = jnp.asarray([0, 1], jnp.int32)
        prompt = jnp.asarray(PROMPTS[0], jnp.int32)[None, :]
        calls = {
            "prefill_tp": lambda: kv.prefill_tp(
                cfg, params, kv.init_kv_cache_tp(cfg, 1, 1), prompt),
            "prefill_chunk_tp": lambda: kv.prefill_chunk_tp(
                cfg, params,
                jax.tree.map(lambda a: a[:, :0],
                             kv.init_kv_cache_tp(cfg, 1, 1)), prompt),
            "decode_step_tp": lambda: kv.decode_step_tp(
                cfg, params, kv.init_kv_cache_tp(cfg, 2, 1), tokens, pos),
            "decode_step_paged": lambda: kv.decode_step_paged(
                cfg, params, kv.init_kv_pool_tp(cfg, 12, 4, 1),
                jnp.arange(12, dtype=jnp.int32).reshape(2, 6), tokens,
                pos),
        }
        with pytest.raises(mpi.CommError, match="KDA mixer.*recurrent"):
            calls[entry]()


class TestZero3Admission:
    def test_admit_zero3_matches_gather_then_slice(self):
        params = _params(CFG)

        def fn(rank):
            from mpi4torch_tpu.parallel import zero as Z

            comm = mpi.COMM_WORLD
            p_shards = Z.zero3_shard_params(comm, params)
            got = kv.admit_zero3(CFG, comm, p_shards, params)
            want = kv.shard_params_tp(
                CFG, Z.zero3_params(comm, p_shards, params), comm)
            same = jax.tree.map(
                lambda a, b: bool(jnp.array_equal(a, b)), got, want)
            return all(jax.tree.leaves(same))

        assert all(mpi.run_ranks(fn, 4, timeout=120.0))

    def test_admit_zero3_serving_dtype_override(self):
        params = _params(CFG)

        def fn(rank):
            from mpi4torch_tpu.parallel import zero as Z

            comm = mpi.COMM_WORLD
            p_shards = Z.zero3_shard_params(comm, params)
            got = kv.admit_zero3(CFG, comm, p_shards, params,
                                 dtype=jnp.float32)
            return all(leaf.dtype == jnp.float32
                       for leaf in jax.tree.leaves(got))

        assert all(mpi.run_ranks(fn, 2, timeout=120.0))


class TestFaultComposition:
    def test_rank_death_mid_decode_attributed(self):
        from mpi4torch_tpu import resilience as rz

        params = _params(CFG)

        def fn(rank):
            eng = serve.Engine(CFG, params, serve.ServeConfig(slots=2))
            eng.submit(PROMPTS[0], max_new=4)
            return eng.run()

        # Prefill issues 2*n_layers Allreduce calls; index 2*n_layers is
        # the FIRST decode-step collective — the fault fires mid-decode.
        with rz.fault_scope([rz.FaultSpec("rank_death", rank=1,
                                          op="Allreduce",
                                          index=2 * CFG.n_layers)]):
            with pytest.raises(mpi.RankFailedError) as ei:
                mpi.run_ranks(fn, 2, timeout=20.0)
        assert ei.value.ranks == frozenset({1})


class TestDeadlinesAndShedding:
    """ISSUE 15: deadline-expired eviction (typed result status, tokens
    a bitwise PREFIX of the per-request generate() oracle) and the
    overload shed policies — identical across the (1,), (4,) and (2,4)
    worlds, because expiry is driven by the engine's injectable clock
    and the host step loop, not by wall time."""

    def _drive_with_deadlines(self, eng, t):
        # rid 0 expires mid-flight (slotted), rid 3 expires while still
        # queued; rids 1/2 run to budget.  The fake clock advances one
        # "second" per step, so the eviction schedule is exact.
        eng.submit(PROMPTS[0], max_new=BUDGETS[0], deadline_s=2.5)
        eng.submit(PROMPTS[1], max_new=BUDGETS[1])
        eng.submit(PROMPTS[2], max_new=BUDGETS[2])
        eng.submit(PROMPTS[3], max_new=BUDGETS[3], deadline_s=1.5)
        expired = []
        for _ in range(32):
            ev = eng.step()
            expired += ev["expired"]
            t[0] += 1.0
            if not eng.pending():
                break
        return expired, eng.results(), eng.statuses()

    def _check(self, expired, results, statuses):
        params = self._params_cache
        assert statuses[0] == serve.STATUS_EXPIRED
        assert statuses[3] == serve.STATUS_EXPIRED
        assert statuses[1] == serve.STATUS_OK
        assert statuses[2] == serve.STATUS_OK
        assert sorted(expired) == [0, 3]
        # Finished requests: full oracle parity.
        for i in (1, 2):
            np.testing.assert_array_equal(
                np.asarray(results[i]),
                oracle_tokens(CFG, params, PROMPTS[i], BUDGETS[i]))
        # The slotted eviction kept an oracle PREFIX (it decoded >= 1
        # token before expiring); the queued eviction is a bare prompt.
        want0 = oracle_tokens(CFG, params, PROMPTS[0], BUDGETS[0])
        got0 = np.asarray(results[0])
        assert len(PROMPTS[0]) < len(got0) < len(want0)
        np.testing.assert_array_equal(got0, want0[:len(got0)])
        np.testing.assert_array_equal(np.asarray(results[3]),
                                      np.asarray(PROMPTS[3], np.int64))

    @pytest.mark.parametrize("world", ["local1", "spmd4", "mesh2x4"])
    def test_deadline_evictions_bitwise_vs_oracle(self, world):
        params = self._params_cache = _params(CFG)
        t = [0.0]
        kw = {"clock": lambda: t[0]}
        if world == "spmd4":
            kw.update(spmd=True, nranks=4)
        elif world == "mesh2x4":
            mesh = mpi.device_mesh({"dp": 2, "tp": 4})
            kw.update(spmd=True, mesh=mesh, axis_name="tp")
        eng = serve.Engine(CFG, params, serve.ServeConfig(slots=2), **kw)
        self._check(*self._drive_with_deadlines(eng, t))
        snap = eng.stats.snapshot()
        assert snap["deadline_expired"] == 2
        assert snap["finished"] == 2

    @pytest.mark.parametrize("policy", sorted(serve.SHED_POLICIES))
    def test_shed_policy_typed_and_bitwise(self, policy):
        params = _params(CFG)
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=1, queue_limit=2,
                                             shed_policy=policy))
        eng.submit(PROMPTS[0], max_new=4)
        eng.step()                      # rid 0 takes (and keeps) the slot
        eng.submit(PROMPTS[1], max_new=2)
        eng.submit(PROMPTS[2], max_new=2)
        eng.submit(PROMPTS[3], max_new=2)   # overflow -> shed
        # The victim is chosen among QUEUED requests at submit time:
        # oldest = rid 1, newest = rid 2 (rid 3 is not queued yet).
        victim = 1 if policy == "drop_oldest" else 2
        assert eng.status(victim) == serve.STATUS_SHED
        np.testing.assert_array_equal(
            np.asarray(eng.results()[victim]),
            np.asarray(PROMPTS[victim], np.int64))
        res = eng.run()
        survivors = [r for r in (0, 1, 2, 3) if r != victim]
        for i in survivors:
            assert eng.status(i) == serve.STATUS_OK
            np.testing.assert_array_equal(
                np.asarray(res[i]),
                oracle_tokens(CFG, params, PROMPTS[i], 4 if i == 0
                              else 2))
        assert eng.stats.snapshot()["shed"] == 1

    def test_shed_policy_none_keeps_queue_full_error(self):
        params = _params(CFG)
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=1, queue_limit=0))
        eng.submit(PROMPTS[0], max_new=4)
        eng.step()      # rid 0 occupies the only slot; queue bound is 0
        with pytest.raises(serve.QueueFullError):
            eng.submit(PROMPTS[1], max_new=2)

    def test_submit_validates_deadline(self):
        params = _params(CFG)
        eng = serve.Engine(CFG, params, serve.ServeConfig(slots=1))
        with pytest.raises(ValueError, match="deadline_s"):
            eng.submit(PROMPTS[0], deadline_s=0.0)

    def test_readmit_expired_ticket_surfaces_typed_status(self):
        """A drained ticket whose remaining deadline budget is consumed
        by resize downtime must NOT vanish at re-admission: readmit
        records it on the destination engine as a typed
        ``deadline_expired`` result carrying the oracle-prefix tokens
        it had earned — and the ticket's deadline travels as a
        REMAINING duration, so source and destination engines with
        different (injected) clocks never mix clock domains."""
        from mpi4torch_tpu.elastic import replan as E
        params = _params(CFG)
        t = [0.0]
        eng = serve.Engine(CFG, params, serve.ServeConfig(slots=2),
                           clock=lambda: t[0])
        eng.submit(PROMPTS[0], max_new=BUDGETS[0], deadline_s=5.0)
        eng.step()      # decodes >= 1 token; deadline still live
        t[0] = 1.0
        tickets, results = E.drain_tickets(eng)
        assert tickets[0].deadline_s == pytest.approx(4.0)
        assert tickets[0].remaining > 0
        # Resize "downtime": the destination engine's clock domain is
        # wildly different (default monotonic would be ~1e5 here); the
        # relative budget makes that irrelevant — only the drained
        # ticket's own remaining seconds count.
        t2 = [100.0]
        eng2 = serve.Engine(CFG, params, serve.ServeConfig(slots=2),
                            clock=lambda: t2[0])
        tickets[0].deadline_s = -0.5    # budget consumed by downtime
        assert E.readmit(eng2, tickets) == []
        assert eng2.status(0) == serve.STATUS_EXPIRED
        stitched = E.stitched_results(eng2.run(), tickets)
        want = oracle_tokens(CFG, params, PROMPTS[0], BUDGETS[0])
        got = np.asarray(stitched[0])
        assert len(PROMPTS[0]) < len(got) < len(want)
        np.testing.assert_array_equal(got, want[:len(got)])
        assert eng2.stats.snapshot()["deadline_expired"] == 1
        # A live budget re-admits through the ordinary path unchanged.
        eng3 = serve.Engine(CFG, params, serve.ServeConfig(slots=2),
                            clock=lambda: t2[0])
        tickets[0].deadline_s = 4.0
        assert E.readmit(eng3, tickets) == [0]
        np.testing.assert_array_equal(
            np.asarray(E.stitched_results(eng3.run(), tickets)[0]), want)

    def test_pop_results_drops_statuses(self):
        t = [0.0]
        params = _params(CFG)
        eng = serve.Engine(CFG, params, serve.ServeConfig(slots=1),
                           clock=lambda: t[0])
        eng.submit(PROMPTS[0], max_new=2, deadline_s=0.5)
        t[0] = 1.0
        eng.step()
        assert eng.status(0) == serve.STATUS_EXPIRED
        eng.pop_results()
        assert eng.status(0) is None
        assert eng.statuses() == {}


# ---------------------------------------------------------------------------
# Paged KV cache: block-table paging, COW prefix sharing, chunked
# prefill (ISSUE 17).
# ---------------------------------------------------------------------------

# Tight pool: 4 requests' pages churn through it (dense-equivalent
# would be slots * max_seq / bs = 12 pages; 5 forces reuse + cached-
# page eviction).  bs=4 divides CFG.max_seq=24.
PAGED_TIGHT = dict(slots=2, block_size=4, num_blocks=5)


class TestPagedOracleParity:
    """Bitwise token parity vs per-request generate() with the KV cache
    paged — across block churn (tight pool), every policy, Mode A and
    Mode B, greedy and sampled."""

    @pytest.mark.parametrize("policy", sorted(serve.POLICIES))
    def test_local_churn_matrix(self, policy):
        params = _params(CFG)
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(policy=policy,
                                             **PAGED_TIGHT))
        assert_matches_oracle(CFG, params, drive(eng))

    @pytest.mark.slow  # heavyweight compile/run; TPU-manual lane (tier-1 budget)
    def test_gqa_rope_swiglu_variants(self):
        for cfg in (CFG_GQA, CFG_ROPE, CFG_SWIGLU):
            params = _params(cfg)
            eng = serve.Engine(cfg, params,
                               serve.ServeConfig(**PAGED_TIGHT))
            assert_matches_oracle(cfg, params, drive(eng))

    @pytest.mark.slow  # serve-smoke carries the paged Mode A (4,) parity cell
    def test_spmd_world4_overlap(self):
        params = _params(CFG)
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(overlap=True,
                                             **PAGED_TIGHT),
                           spmd=True, nranks=4)
        assert_matches_oracle(CFG, params, drive(eng))

    @pytest.mark.slow  # heavyweight compile/run; TPU-manual lane (tier-1 budget)
    def test_spmd_mesh_2x4(self):
        params = _params(CFG)
        mesh = mpi.device_mesh({"dp": 2, "tp": 4})
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(overlap=True,
                                             **PAGED_TIGHT),
                           spmd=True, mesh=mesh, axis_name="tp")
        assert_matches_oracle(CFG, params, drive(eng))

    @pytest.mark.slow  # heavyweight compile/run; TPU-manual lane (tier-1 budget)
    def test_ranks_world4_mode_b(self):
        params = _params(CFG)

        def fn(rank):
            eng = serve.Engine(CFG, params,
                               serve.ServeConfig(overlap=True,
                                                 **PAGED_TIGHT))
            return drive(eng)

        outs = mpi.run_ranks(fn, 4, timeout=120.0)
        # Identical deterministic host decisions on every rank keep
        # the block tables in lock-step under the decode collectives.
        for r in range(1, 4):
            for i in range(len(PROMPTS)):
                np.testing.assert_array_equal(outs[r][i], outs[0][i])
        assert_matches_oracle(CFG, params, outs[0])

    @pytest.mark.parametrize("spmd", SPMD, ids=SPMD_IDS)
    def test_sampled_parity_local(self, spmd):
        params = _params(CFG)
        keys = [jax.random.PRNGKey(100 + i) for i in range(len(PROMPTS))]
        eng = serve.Engine(
            CFG, params,
            serve.ServeConfig(temperature=0.9, top_k=7, **PAGED_TIGHT),
            **spmd)
        res = drive(eng, keys=keys)
        assert_matches_oracle(CFG, params, res, temperature=0.9,
                              top_k=7, keys=keys)

    def test_preemption_under_pool_pressure(self):
        # 3 pages for two slots whose requests need 2 pages each: the
        # second admission eventually starves the first of a decode
        # page — the newest-admitted is preempted, requeued with its
        # emitted tokens folded into the prompt, and the STITCHED
        # stream stays bitwise the oracle.
        params = _params(CFG)
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=2, block_size=4,
                                             num_blocks=3))
        eng.submit(PROMPTS[0], max_new=6)   # 3+6-1=8 rows -> 2 pages
        eng.submit(PROMPTS[1], max_new=4)   # 5+4-1=8 rows -> 2 pages
        res = eng.run()
        for i, n in ((0, 6), (1, 4)):
            np.testing.assert_array_equal(
                res[i], oracle_tokens(CFG, params, PROMPTS[i], n))
        assert eng.stats.snapshot()["preempted"] >= 1

    def test_deadline_evictions_compose(self):
        # PR 15 deadline path on the paged engine: the expired request
        # keeps an oracle PREFIX, survivors stay bitwise, pages return
        # to the pool.
        t = [0.0]
        params = _params(CFG)
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(**PAGED_TIGHT),
                           clock=lambda: t[0])
        eng.submit(PROMPTS[0], max_new=6, deadline_s=2.5)
        eng.submit(PROMPTS[1], max_new=4)
        while eng.pending():
            eng.step()
            t[0] += 1.0
        res = eng.results()
        assert eng.status(0) == serve.STATUS_EXPIRED
        want0 = oracle_tokens(CFG, params, PROMPTS[0], 6)
        got0 = np.asarray(res[0])
        np.testing.assert_array_equal(got0, want0[:len(got0)])
        assert len(got0) < len(want0)
        np.testing.assert_array_equal(
            res[1], oracle_tokens(CFG, params, PROMPTS[1], 4))
        assert eng._mgr.blocks_in_use == 0


class TestPrefixSharing:
    def test_shared_prefix_prefilled_once_same_pages(self):
        params = _params(CFG)
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=2, block_size=4))
        sys_p = np.arange(1, 9)                  # 8 tokens = 2 pages
        pa = np.concatenate([sys_p, [20, 21]])
        pb = np.concatenate([sys_p, [22]])
        ra = eng.submit(pa, max_new=4)
        rb = eng.submit(pb, max_new=4)
        eng.step()                # both admitted: tables live now
        sa = [s for r, s in eng.slot_log if r == ra][0]
        sb = [s for r, s in eng.slot_log if r == rb][0]
        shared = list(eng._table[sb][:2])
        assert list(eng._table[sa][:2]) == shared
        assert min(shared) >= 0
        res = eng.run()
        np.testing.assert_array_equal(
            res[ra], oracle_tokens(CFG, params, pa, 4))
        np.testing.assert_array_equal(
            res[rb], oracle_tokens(CFG, params, pb, 4))
        snap = eng.stats.snapshot()
        # The census: the 8 shared tokens prefill ONCE.
        assert snap["prefill_tokens"] == len(pa) + (len(pb) - 8)
        assert snap["prefix_hits"] == 1
        assert snap["prefix_misses"] == 1

    def test_partial_tail_hit_is_cow_copied(self):
        # pa's 6-token prompt with bs=4 REGISTERS as one full page plus
        # a 2-row partial tail (a full-page chain cannot represent it).
        # pb extends that exact prefix, so its match lands mid-page on
        # the tail — which must be COPIED before pb's suffix rows hit
        # it (never written in place: pa still attends those rows).
        params = _params(CFG)
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=2, block_size=4))
        pa = np.arange(1, 7)                     # 6 tokens
        pb = np.concatenate([pa, [22, 23]])
        ra = eng.submit(pa, max_new=4)
        rb = eng.submit(pb, max_new=4)
        eng.step()
        # Shared FULL page identical; tail pages distinct (the copy).
        sa = [s for r, s in eng.slot_log if r == ra][0]
        sb = [s for r, s in eng.slot_log if r == rb][0]
        assert eng._table[sa][0] == eng._table[sb][0] >= 0
        assert eng._table[sa][1] != eng._table[sb][1]
        res = eng.run()
        np.testing.assert_array_equal(
            res[ra], oracle_tokens(CFG, params, pa, 4))
        np.testing.assert_array_equal(
            res[rb], oracle_tokens(CFG, params, pb, 4))
        snap = eng.stats.snapshot()
        assert snap["cow_copies"] >= 1
        assert snap["prefix_hits"] == 1

    def test_prefix_cache_off_still_bitwise(self):
        params = _params(CFG)
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=2, block_size=4,
                                             prefix_cache=False))
        sys_p = np.arange(1, 9)
        pa = np.concatenate([sys_p, [20]])
        pb = np.concatenate([sys_p, [21]])
        eng.submit(pa, max_new=3)
        eng.submit(pb, max_new=3)
        res = eng.run()
        np.testing.assert_array_equal(
            res[0], oracle_tokens(CFG, params, pa, 3))
        np.testing.assert_array_equal(
            res[1], oracle_tokens(CFG, params, pb, 3))
        snap = eng.stats.snapshot()
        assert snap["prefix_hits"] == 0
        assert snap["prefill_tokens"] == len(pa) + len(pb)

    def test_cache_dtype_gate_disables_sharing_not_paging(self):
        # A down-cast cache would re-quantize shared rows the oracle
        # keeps at compute precision: the exactness gate turns the
        # prefix index (and chunking) off while paging stays on.
        params = _params(CFG)
        eng = serve.Engine(
            CFG, params,
            serve.ServeConfig(slots=2, block_size=4,
                              cache_dtype=jnp.bfloat16))
        assert eng._mgr.block_size == 4
        assert not eng._mgr.prefix_cache
        assert eng._chunk is None
        sys_p = np.arange(1, 9)
        eng.submit(np.concatenate([sys_p, [20]]), max_new=2)
        eng.submit(np.concatenate([sys_p, [21]]), max_new=2)
        eng.run()
        assert eng.stats.snapshot()["prefix_hits"] == 0


class TestChunkedPrefill:
    @pytest.mark.parametrize("chunk", [
        1, 3,
        # block-aligned + oversize chunks ride the TPU-manual lane
        # (tier-1 budget); 1 and 3 cover the mid-page boundary cases.
        pytest.param(4, marks=pytest.mark.slow),
        pytest.param(7, marks=pytest.mark.slow),
    ])
    def test_chunked_prefill_bitwise(self, chunk):
        params = _params(CFG)
        eng = serve.Engine(
            CFG, params,
            serve.ServeConfig(slots=2, block_size=4,
                              prefill_chunk=chunk))
        assert_matches_oracle(CFG, params, drive(eng))

    @pytest.mark.parametrize("nranks", [1, 4])
    def test_chunk_from_empty_past_is_the_whole_prefill(self, nranks):
        # The two prefill views meet at an empty prefix: the chunk's
        # rows and logits are bitwise what prefill_tp writes at 0.
        params = _params(CFG_ROPE)
        prompt = jnp.asarray(PROMPTS[1], jnp.int32)[None, :]
        hd = CFG_ROPE.d_model // CFG_ROPE.n_heads

        def both(prompt):
            comm = mpi.COMM_WORLD
            sh = kv.shard_params_tp(CFG_ROPE, params, comm)
            cache = kv.init_kv_cache_tp(CFG_ROPE, 1, nranks, jnp.float64)
            empty = jnp.zeros(
                (1, 0, CFG_ROPE.kv_heads // nranks, hd), jnp.float64)
            past = [{"k": empty, "v": empty}] * CFG_ROPE.n_layers
            whole = kv.prefill_tp(CFG_ROPE, sh, cache, prompt, comm)
            chunk = kv.prefill_chunk_tp(CFG_ROPE, sh, past, prompt, comm)
            return whole, chunk

        (l_whole, cache), (l_chunk, rows) = \
            mpi.run_spmd(both, nranks=nranks)(prompt)
        np.testing.assert_array_equal(np.asarray(l_whole),
                                      np.asarray(l_chunk))
        n = prompt.shape[1]
        for c, r in zip(cache, rows):
            for leaf in ("k", "v"):
                assert r[leaf].shape[2] == n
                np.testing.assert_array_equal(
                    np.asarray(c[leaf][:, :, :n]), np.asarray(r[leaf]))

    def test_long_prompt_never_stalls_resident_decode(self):
        # THE TTFT-bound regression: while a long prompt lands chunk by
        # chunk, the already-resident slot must emit one token on EVERY
        # step — chunked prefill interleaves, it does not stall.
        params = _params(CFG)
        eng = serve.Engine(
            CFG, params,
            serve.ServeConfig(slots=2, block_size=4, prefill_chunk=2))
        r0 = eng.submit(PROMPTS[0], max_new=10)
        eng.step()                       # r0 resident, decoding
        long_p = np.arange(1, 13)        # 12 tokens -> 6 chunks of 2
        r1 = eng.submit(long_p, max_new=3)
        stall_free_steps = 0
        while eng._prefill_jobs:
            ev = eng.step()
            assert r0 in ev["emitted"], \
                "resident decode stalled during chunked prefill"
            stall_free_steps += 1
        assert stall_free_steps >= 5     # the job really spanned steps
        res = eng.run()
        np.testing.assert_array_equal(
            res[r0], oracle_tokens(CFG, params, PROMPTS[0], 10))
        np.testing.assert_array_equal(
            res[r1], oracle_tokens(CFG, params, long_p, 3))

    def test_unchunked_long_prompt_admission_is_atomic(self):
        # Control for the test above: without prefill_chunk the same
        # admission runs the whole prompt in one step (dense
        # semantics), so the chunked path is what bounds it.
        params = _params(CFG)
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=2, block_size=4))
        r1 = eng.submit(np.arange(1, 13), max_new=3)
        ev = eng.step()
        assert r1 in ev["admitted"]
        assert not eng._prefill_jobs


class TestPagedPoolAccounting:
    def test_block_level_counters_and_census(self):
        params = _params(CFG)
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=2, block_size=4,
                                             num_blocks=6))
        eng.submit(PROMPTS[0], max_new=4)     # 3 tokens -> 1 page
        eng.step()
        snap = eng.stats.snapshot()
        assert snap["blocks_in_use"] == eng._mgr.blocks_in_use > 0
        assert snap["blocks_in_use"] + snap["blocks_free"] \
            + snap["blocks_cached"] == 6
        hd = CFG.d_model // CFG.n_heads
        row = 2 * CFG.kv_heads * hd * CFG.n_layers \
            * jnp.dtype(eng._dtype).itemsize
        assert eng.kv_bytes_resident() \
            == eng._mgr.blocks_in_use * 4 * row
        # Dense census for comparison: full max_seq rows per occupied
        # slot — the paged engine's residency is strictly smaller for
        # a short sequence.
        dense = serve.Engine(CFG, params, serve.ServeConfig(slots=2))
        dense.submit(PROMPTS[0], max_new=4)
        dense.step()
        assert dense.kv_bytes_resident() == CFG.max_seq * row
        assert eng.kv_bytes_resident() < dense.kv_bytes_resident()

    def test_submit_rejects_request_larger_than_pool(self):
        params = _params(CFG)
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=1, block_size=4,
                                             num_blocks=2))
        with pytest.raises(ValueError, match="pages"):
            eng.submit(np.arange(1, 10), max_new=8)   # needs 4 pages

    def test_config_validation(self):
        params = _params(CFG)
        with pytest.raises(ValueError, match="divide"):
            serve.Engine(CFG, params,
                         serve.ServeConfig(slots=1, block_size=5))
        with pytest.raises(ValueError, match="block_size"):
            serve.ServeConfig(block_size=-1)
        with pytest.raises(ValueError, match="num_blocks"):
            serve.ServeConfig(block_size=4, num_blocks=0)
        with pytest.raises(ValueError, match="prefill_chunk"):
            serve.ServeConfig(prefill_chunk=2)       # needs paging
        with pytest.raises(ValueError, match="prefill_chunk"):
            serve.ServeConfig(block_size=4, prefill_chunk=0)

    def test_registry_sync_guard(self):
        from mpi4torch_tpu.analyze.registry import serve_paging_problems

        assert serve_paging_problems() == []


class TestOneCacheManager:
    """``ServeConfig(block_size=0)`` is the paged pool at one page of
    ``max_seq`` tokens a slot (``num_blocks = slots``, nothing shared):
    the engine has one cache manager and no fork on the page size."""

    @pytest.mark.parametrize("temperature", [0.0, 0.8],
                             ids=["greedy", "sampled"])
    @pytest.mark.parametrize(
        "spmd", [{}, {"spmd": True, "nranks": 1},
                 {"spmd": True, "nranks": 4}],
        ids=["eager", "spmd1", "spmd4"])
    def test_block_size_zero_is_one_page_a_slot(self, spmd, temperature):
        params = _params(CFG)
        slots = 2
        keys = None if not temperature else [
            jax.random.PRNGKey(7 + i) for i in range(len(PROMPTS))]

        def served(**paging):
            eng = serve.Engine(
                CFG, params,
                serve.ServeConfig(slots=slots, temperature=temperature,
                                  top_k=5 if temperature else 0, **paging),
                **spmd)
            for i, (p, n) in enumerate(zip(PROMPTS, BUDGETS)):
                eng.submit(p, max_new=n,
                           key=None if keys is None else keys[i])
            resident = []
            while eng.pending():           # 4 requests through 2 slots
                eng.step()
                resident.append(eng.kv_bytes_resident())
            return eng, resident

        plain, resident = served()
        paged, resident_paged = served(
            block_size=CFG.max_seq, num_blocks=slots, prefix_cache=False)
        assert plain.results().keys() == paged.results().keys()
        for rid, toks in plain.results().items():
            np.testing.assert_array_equal(toks, paged.results()[rid])
        assert plain.slot_log == paged.slot_log
        assert len(plain.slot_log) > slots          # slots were reused
        assert plain.statuses() == paged.statuses()
        assert resident == resident_paged and max(resident) > 0
        if spmd:
            assert plain.lower_step().as_text(debug_info=False) \
                == paged.lower_step().as_text(debug_info=False)


class TestPagedNoRetrace:
    def test_lowered_step_identical_across_table_states(self):

        params = _params(CFG)
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=2, block_size=4,
                                             overlap=True),
                           spmd=True, nranks=4)
        eng.submit(PROMPTS[0], max_new=6)
        eng.step()
        txt1 = eng.lower_step().as_text(debug_info=False)
        eng.submit(PROMPTS[1], max_new=4)
        eng.step()
        txt2 = eng.lower_step().as_text(debug_info=False)
        assert txt1 == txt2
        assert txt1.count('"stablehlo.gather"') >= 2 * CFG.n_layers


def _loop_install(eng, pool, rows, j, lo, hi):
    """The per-page, per-leaf install the engine had before ISSUE 26,
    kept here as the reference: one plain ``.at[].set`` at concrete
    page ids for every page and cache leaf.  Returns the new pool."""
    bs = eng.serve_cfg.block_size
    for bi in range(lo // bs, -(-hi // bs)):
        b = int(eng._table[j, bi])
        r0, r1 = max(lo, bi * bs), min(hi, (bi + 1) * bs)
        o0 = r0 - bi * bs
        if eng._spmd:
            pool = jax.tree.map(
                lambda s, r: s.at[:, b, o0:o0 + (r1 - r0)].set(
                    r[:, 0, r0 - lo:r1 - lo].astype(s.dtype)), pool, rows)
        else:
            pool = jax.tree.map(
                lambda s, r: s.at[b, o0:o0 + (r1 - r0)].set(
                    r[0, r0 - lo:r1 - lo].astype(s.dtype)), pool, rows)
    return pool


def _seeded_pool(eng, seed):
    """The engine's pool refilled with seeded random bits, laid out
    as it was."""
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda a: jax.device_put(
            jnp.asarray(rng.standard_normal(a.shape), a.dtype),
            a.sharding), eng._cache)


def _seeded_rows(eng, seed, n_rows, dtype=None):
    """Seeded prefill rows of ``n_rows`` positions for the engine's
    pool: ``(1, n_rows, kvh, hd)`` leaves, stacked under SPMD."""
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda a: jnp.asarray(
            rng.standard_normal(a.shape[:-4] + (1, n_rows) + a.shape[-2:]),
            dtype or a.dtype), eng._cache)


def _positions(eng, j, lo, hi):
    """``(page id, offset)`` of positions ``lo..hi-1`` of slot ``j``."""
    bs = eng.serve_cfg.block_size
    return [(int(eng._table[j, t // bs]), t % bs) for t in range(lo, hi)]


class TestCompiledInstall:
    """ISSUE 26: ``Engine._install_rows`` is one compiled, donated call
    whatever the range; page ids, offset and length are data."""

    BS, SLOTS = 4, 3
    # (lo, hi, row positions); None = whole-prompt rows (max_seq).
    RANGES = [
        pytest.param(0, 8, None, id="whole-pages-from-0"),
        pytest.param(0, 10, None, id="hi-inside-a-page"),
        pytest.param(6, 12, 6, id="lo-inside-a-page"),
        pytest.param(5, 7, 2, id="lo-and-hi-in-one-page"),
        pytest.param(3, 10, 7, id="chunk-over-three-pages"),
        pytest.param(9, 10, 1, id="one-token-suffix"),
        pytest.param(4, 16, 12, id="whole-pages-from-4"),
    ]

    def _engine(self, spmd, **kw):
        return serve.Engine(
            CFG, _params(CFG),
            serve.ServeConfig(slots=self.SLOTS, block_size=self.BS, **kw),
            spmd=spmd, nranks=2 if spmd else None)

    @pytest.mark.parametrize("spmd", [False, True], ids=["eager", "spmd"])
    @pytest.mark.parametrize("lo,hi,n_rows", RANGES)
    def test_bitwise_vs_per_page_loop(self, spmd, lo, hi, n_rows):
        eng = self._engine(spmd)
        j = 1
        # Scattered, non-monotone page ids: nothing may lean on order.
        eng._table[j, :] = [7, 2, 11, 5, 16, 0]
        eng._cache = _seeded_pool(eng, 26)
        before = jax.tree.map(np.asarray, eng._cache)
        rows = _seeded_rows(eng, 27, n_rows or CFG.max_seq)
        want = jax.tree.map(
            np.asarray, _loop_install(eng, eng._cache, rows, j, lo, hi))
        writes = eng.stats.counters.get("install_writes", 0)
        eng._install_rows(j, rows, lo, hi)
        assert eng.stats.counters["install_writes"] == writes + 1
        got = jax.tree.map(np.asarray, eng._cache)
        written = np.zeros(before[0]["k"].shape[-4:-2], bool)
        for b, o in _positions(eng, j, lo, hi):
            written[b, o] = True
        for g, w, b, r in zip(*map(jax.tree.leaves,
                                   (got, want, before, rows))):
            np.testing.assert_array_equal(g, w)
            # Every row outside lo..hi-1 keeps its bits; the rows
            # inside are the prefill's.
            np.testing.assert_array_equal(g[..., ~written, :, :],
                                          b[..., ~written, :, :])
            np.testing.assert_array_equal(
                np.stack([g[..., b, o, :, :]
                          for b, o in _positions(eng, j, lo, hi)], -3),
                np.asarray(r)[..., 0, :hi - lo, :, :])
        if spmd:
            # The state keeps the layout run_spmd's outputs carry.
            assert jax.tree.leaves(eng._cache)[0].sharding \
                == jax.tree.leaves(eng._shards)[0].sharding

    @pytest.mark.parametrize("spmd", [False, True], ids=["eager", "spmd"])
    def test_rows_are_cast_to_the_pool_dtype(self, spmd):
        eng = self._engine(spmd, cache_dtype=jnp.float32)
        eng._table[0, :3] = [4, 9, 1]
        eng._cache = _seeded_pool(eng, 28)
        rows = _seeded_rows(eng, 29, 7, jnp.float64)
        want = jax.tree.map(
            np.asarray, _loop_install(eng, eng._cache, rows, 0, 2, 9))
        eng._install_rows(0, rows, 2, 9)
        for g, w in zip(jax.tree.leaves(eng._cache),
                        jax.tree.leaves(want)):
            assert g.dtype == jnp.float32
            np.testing.assert_array_equal(np.asarray(g), w)

    @pytest.mark.parametrize("spmd", [False, True], ids=["eager", "spmd"])
    def test_one_program_for_every_whole_prompt_install(self, spmd):
        """Different page ids, slots and prompt lengths, with decode
        steps (page churn, the state's round trip through the step) in
        between: the second install compiles nothing."""
        events = []

        def on(event, _secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                events.append(event)

        eng = self._engine(spmd)
        ra = eng.submit(np.arange(1, 4), max_new=6)       # 1 page
        eng.step()
        eng.step()
        # (Eager engines share one jit, and with it its programs.)
        programs = eng._install_call._cache_size()
        # Rows as the prefill hands them over (its own program
        # compiles here, outside the count).
        _, rows = eng._prefill_full(np.arange(5, 18))
        eng._table[2, :4] = [15, 3, 12, 8]
        jax.monitoring.register_event_duration_secs_listener(on)
        try:
            eng._install_rows(2, rows, 0, 13)              # 4 pages
        finally:
            jax.monitoring.unregister_event_duration_listener(on)
        assert events == []
        eng._table[2, :] = -1
        rb = eng.submit(np.arange(5, 16), max_new=4)      # 3 pages
        res = eng.run()
        assert eng._install_call._cache_size() == programs
        assert eng.stats.counters["install_writes"] == 3
        np.testing.assert_array_equal(
            res[ra], oracle_tokens(CFG, _params(CFG), np.arange(1, 4), 6))
        np.testing.assert_array_equal(
            res[rb], oracle_tokens(CFG, _params(CFG), np.arange(5, 16), 4))

    @pytest.mark.parametrize("spmd", [False, True],
                             ids=["eager", "spmd-built-with-jit-off"])
    def test_first_install_of_a_fresh_engine_donates(self, spmd):
        """A fresh pool's leaves must be buffers of their own (the
        templates of ``kv`` share one): one buffer cannot be donated
        twice in a call.  The SPMD engine is built as the benchmark
        builds it, with jit disabled, where the install must not be
        called."""
        if spmd:
            with jax.disable_jit():
                eng = self._engine(True)
        else:
            eng = self._engine(False)
        leaves = jax.tree.leaves(eng._cache)
        assert len({s.data.unsafe_buffer_pointer() for a in leaves
                    for s in a.addressable_shards}) \
            == len(leaves) * (2 if spmd else 1)
        eng.submit(PROMPTS[1], max_new=4)
        eng.step()
        # Donated: the old leaves are gone, the engine holds new ones.
        assert all(a.is_deleted() for a in leaves)
        assert not any(a.is_deleted()
                       for a in jax.tree.leaves(eng._cache))
        np.testing.assert_array_equal(
            eng.run()[0], oracle_tokens(CFG, _params(CFG), PROMPTS[1], 4))

    def test_index_must_name_the_static_page_count(self):
        pool = kv.init_kv_pool_tp(CFG, 6, 4, 1, jnp.float64)
        rows = jax.tree.map(lambda a: a[None, :1, 0].repeat(5, 1), pool)
        assert kv.install_page_count(5, 4) == 2
        with pytest.raises(ValueError, match="need 2"):
            kv.install_rows_paged(pool, rows,
                                  np.array([0, 5, 1, 2, 3], np.int32))


class TestPagedStepInPlace:
    """ISSUE 29: the paged decode step takes its pool over and writes
    one row per live slot into it; nothing of a pool leaf's shape is
    selected or broadcast; two counters say how many pages the step's
    attention visited beside how many its live slots hold."""

    BS, SLOTS = 4, 3

    def _engine(self, spmd, **kw):
        return serve.Engine(
            CFG, _params(CFG),
            serve.ServeConfig(slots=self.SLOTS, block_size=self.BS, **kw),
            spmd=spmd, nranks=2 if spmd else None)

    @pytest.mark.parametrize("spmd", [False, True],
                             ids=["eager", "spmd-built-with-jit-off"])
    def test_first_decode_step_of_a_fresh_engine_donates(self, spmd):
        """The step after the admitting one runs no install: what takes
        the pool over there is the decode step itself."""
        if spmd:
            with jax.disable_jit():
                eng = self._engine(True)
        else:
            eng = self._engine(False)
        eng.submit(PROMPTS[1], max_new=4)
        eng.step()                       # install + first decode step
        layout = jax.tree.leaves(eng._cache)[0].sharding
        leaves = jax.tree.leaves(eng._cache)
        before = eng.stats.counters["install_writes"]
        eng.step()                       # a decode step and nothing else
        assert eng.stats.counters["install_writes"] == before
        assert all(a.is_deleted() for a in leaves)
        now = jax.tree.leaves(eng._cache)
        assert not any(a.is_deleted() for a in now)
        # the state keeps its layout through the donated step
        assert all(a.sharding == layout for a in now)
        np.testing.assert_array_equal(
            eng.run()[0], oracle_tokens(CFG, _params(CFG), PROMPTS[1], 4))

    @pytest.mark.parametrize("spmd", [False, True], ids=["eager", "spmd"])
    def test_only_the_written_cells_change(self, spmd):
        """One decode step over a seeded pool: every cell but the live
        slots' ``(page, offset)`` keeps its bits (free slots and their
        ``-1`` ids write nothing, and not into the last page)."""
        eng = self._engine(spmd)
        eng.submit(PROMPTS[0], max_new=6)       # 3 tokens: page 0
        eng.submit(PROMPTS[1], max_new=4)       # 5 tokens: pages 0-1
        eng.step()
        eng._cache = _seeded_pool(eng, 29)
        before = jax.tree.map(np.asarray, eng._cache)
        eng.step()
        # The step wrote at the position each slot has just left (the
        # first slot's row opened a new page, mapped inside the step).
        cells = [(int(eng._table[j, (eng._pos[j] - 1) // self.BS]),
                  int((eng._pos[j] - 1) % self.BS)) for j in (0, 1)]
        assert cells[0][1] == 0
        written = np.zeros(before[0]["k"].shape[-4:-2], bool)
        for b, o in cells:
            written[b, o] = True
        for got, old in zip(jax.tree.leaves(eng._cache),
                            jax.tree.leaves(before)):
            got = np.asarray(got)
            np.testing.assert_array_equal(got[..., ~written, :, :],
                                          old[..., ~written, :, :])
            assert (got[..., written, :, :]
                    != old[..., written, :, :]).all()

    def test_lowered_step_selects_and_broadcasts_nothing_pool_shaped(self):
        import re

        eng = serve.Engine(
            CFG, _params(CFG),
            serve.ServeConfig(slots=self.SLOTS, block_size=self.BS,
                              num_blocks=7), spmd=True, nranks=2)
        eng.submit(PROMPTS[0], max_new=6)
        eng.step()
        text = eng.lower_step().as_text(debug_info=False)
        hd = CFG.d_model // CFG.n_heads
        leaf = f"{7}x{self.BS}x{CFG.n_heads // 2}x{hd}xf64>"
        # run_spmd stacks a rank's new leaf under a leading axis of 1 (a
        # broadcast_in_dim that moves nothing): not an instruction that
        # fills a leaf, and told apart by its operand.
        stacking = f"(tensor<{leaf}) -> tensor<1x{leaf}"
        made = [m.group(1) for m in re.finditer(
            r"= \"?stablehlo\.(\w+)([^\n]*)tensor<(?:\d+x)?"
            + re.escape(leaf) + r"\n", text)
            if stacking not in m.group(0)]
        # a leaf is sliced off the stacked state and written; nothing is
        # selected into one or broadcast over one
        assert sorted(set(made)) == ["dynamic_slice", "reshape"], made
        assert text.count('"stablehlo.scatter"(') == 2 * CFG.n_layers

    def test_page_counters_against_hand_counted_pages(self, monkeypatch):
        eng = self._engine(False)
        n_blk = CFG.max_seq // self.BS
        eng.submit(np.arange(1, 4), max_new=4)      # 3 tokens
        eng.submit(np.arange(1, 10), max_new=3)     # 9 tokens
        from mpi4torch_tpu.utils import profiling
        log0 = len(profiling.serve_step_log())
        eng.run()
        recs = profiling.serve_step_log()[log0:]
        # Positions each decode step attends from: the first request
        # decodes at 3, 4, 5 (its 4th token needs no write), the second
        # at 9, 10.
        want_live = [(3 // 4 + 1) + (9 // 4 + 1),
                     (4 // 4 + 1) + (10 // 4 + 1),
                     (5 // 4 + 1)]
        assert [r["decode_pages_live"] for r in recs] == want_live
        # off the TPU the read is the gather: every slot's whole row
        assert [r["decode_pages_read"] for r in recs] \
            == [2 * n_blk, 2 * n_blk, 1 * n_blk]
        assert eng.stats.counters["decode_pages_live"] == sum(want_live)
        assert eng.stats.counters["decode_pages_read"] == 5 * n_blk
        # and no kernel walks a grid
        assert [r["decode_grid_steps"] for r in recs] == [0, 0, 0]
        # an engine whose step compiled the kernel visits what is live
        eng2 = self._engine(False)
        monkeypatch.setattr(eng2, "_kernel_read", True)
        eng2.submit(np.arange(1, 4), max_new=4)
        eng2.submit(np.arange(1, 10), max_new=3)
        eng2.run()
        assert eng2.stats.counters["decode_pages_read"] \
            == eng2.stats.counters["decode_pages_live"] == sum(want_live)

    def test_grid_steps_are_the_kernels_own_grid(self, monkeypatch):
        """An engine whose read is the kernel's counts, a decode step,
        the steps of the grid that kernel walks (every slot's, live or
        free), asked of ``read_grid`` with the pool it made."""
        from mpi4torch_tpu.ops import paged_attention as pa
        from mpi4torch_tpu.utils import profiling
        monkeypatch.setattr(pa, "uses_kernel", lambda q, pool_k: True)
        eng = self._engine(False)
        monkeypatch.undo()            # count as the kernel, read as here
        n_blk = CFG.max_seq // self.BS
        entry = eng._cache[0]
        grid = pa.read_grid(self.SLOTS, n_blk, entry["k"], entry["v"])
        assert grid[0] == self.SLOTS and n_blk % grid[1] == 0
        assert eng._kernel_read and eng._grid_steps == grid[0] * grid[1]
        eng.submit(np.arange(1, 4), max_new=4)
        log0 = len(profiling.serve_step_log())
        eng.run()
        recs = profiling.serve_step_log()[log0:]
        assert [r["decode_grid_steps"] for r in recs] \
            == [eng._grid_steps] * 3
        assert eng.stats.counters["decode_grid_steps"] \
            == 3 * eng._grid_steps

    def test_dense_engine_counts_one_page_a_slot(self):
        """``block_size=0`` is the pool at one page of ``max_seq``
        tokens a slot: each of the request's two decode steps holds,
        and off the TPU gathers, its slot's one page."""
        eng = serve.Engine(CFG, _params(CFG), serve.ServeConfig(slots=2))
        assert (eng._mgr.block_size, eng._mgr.num_blocks) \
            == (CFG.max_seq, 2)
        assert not eng._mgr.prefix_cache
        eng.submit(PROMPTS[0], max_new=3)
        eng.run()
        assert eng.stats.counters["decode_pages_live"] == 2
        assert eng.stats.counters["decode_pages_read"] == 2
        assert eng.stats.counters["decode_grid_steps"] == 0

    def test_engine_asks_the_kernels_own_predicate(self, monkeypatch):
        from mpi4torch_tpu.ops import paged_attention as pa
        asked = []

        def uses_kernel(q, pool_k):
            asked.append((q.shape, str(q.dtype), pool_k.shape))
            return False

        monkeypatch.setattr(pa, "uses_kernel", uses_kernel)
        eng = self._engine(False)
        hd = CFG.d_model // CFG.n_heads
        assert asked == [((self.SLOTS, CFG.n_heads, hd), "float64",
                          (self.SLOTS * CFG.max_seq // self.BS, self.BS,
                           CFG.n_heads, hd))]
        assert eng._kernel_read is False


class TestPagedDrainReadmit:
    def test_tickets_carry_pages_and_readmit_prefix_hits(self):
        # Satellite 6: a drained paged request's ticket carries its
        # block-table state, and re-admission recovers the pages
        # through the prefix index — prefill re-runs ~1 token, and the
        # stitched stream stays bitwise the oracle.
        from mpi4torch_tpu.elastic import replan as E

        params = _params(CFG)
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=2, block_size=4))
        eng.submit(PROMPTS[0], max_new=6)
        eng.submit(PROMPTS[1], max_new=4)
        eng.step(); eng.step()
        tickets, _ = E.drain_tickets(eng)
        for t in tickets:
            assert t.pages is not None
            assert t.pages["n_tokens"] > 0
            assert len(t.pages["block_ids"]) \
                == -(-t.pages["n_tokens"] // 4)
        serve.reset_stats()
        E.readmit(eng, tickets)
        res = eng.run()
        stitched = E.stitched_results(res, tickets)
        np.testing.assert_array_equal(
            stitched[0], oracle_tokens(CFG, params, PROMPTS[0], 6))
        np.testing.assert_array_equal(
            stitched[1], oracle_tokens(CFG, params, PROMPTS[1], 4))
        snap = eng.stats.snapshot()
        assert snap["prefix_hits"] == 2          # both re-admissions hit
        # Each readmission prefilled ONLY its uncovered suffix (1-2
        # tokens past the registered rows), not the whole prompt.
        assert snap["prefill_tokens"] <= 2 * 2


class TestSlotStateOnTheDevice:
    """ISSUE 38: the slot state (tokens, positions, live mask, table)
    stays on the device between decode steps and the host uploads only
    the arrays in which its own state differs from what the device
    holds.  Whatever takes a request out of its slot in the middle of a
    run writes the host's arrays alone; the comparison has to catch it,
    or the other slots, and whoever takes the freed slot, decode from a
    stale state."""

    @staticmethod
    def _undisturbed(params):
        """Every request in a slot of its own to the largest budget a
        case gives it: greedy, so any shorter run is a prefix."""
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=3, block_size=4))
        rids = [eng.submit(p, max_new=10) for p in PROMPTS[:3]]
        res = eng.run()
        return [np.asarray(res[r]) for r in rids]

    @pytest.mark.parametrize("spmd", SPMD, ids=SPMD_IDS)
    @pytest.mark.parametrize(
        "how", ["eviction", "deadline", "preemption", "drain"])
    def test_a_disturbed_slot_leaves_the_others_tokens(self, how, spmd):
        from mpi4torch_tpu.elastic import replan as E
        from mpi4torch_tpu.utils import profiling

        params = _params(CFG)
        want = self._undisturbed(params)
        t = [0.0]
        tight = {"num_blocks": 5} if how == "preemption" else {}
        eng = serve.Engine(
            CFG, params, serve.ServeConfig(slots=2, block_size=4, **tight),
            clock=lambda: t[0], **spmd)
        # Request 1 is the disturbed one; request 0 decodes beside it
        # throughout and request 2 takes whichever slot comes free.
        budgets = [10, 3 if how == "eviction" else 10, 6]
        deadline = 3.5 if how == "deadline" else None
        rids = [eng.submit(p, max_new=n,
                           deadline_s=deadline if i == 1 else None)
                for i, (p, n) in enumerate(zip(PROMPTS[:3], budgets))]
        tickets = None
        for step in range(64):
            if how == "drain" and step == 3:
                tickets, _ = E.drain_tickets(eng)
                assert len(tickets) == 3
                E.readmit(eng, tickets)
            eng.step()
            t[0] += 1.0
            if not eng.pending():
                break
        res = eng.results()
        if tickets is not None:
            res = E.stitched_results(res, tickets)
        snap = eng.stats.snapshot()
        assert snap["evicted"] >= 2
        if how == "preemption":
            assert snap["preempted"] >= 1
        status = eng.statuses()
        for i, rid in enumerate(rids):
            got = np.asarray(res[rid])
            expired = how == "deadline" and i == 1
            assert status[rid] == (serve.STATUS_EXPIRED if expired
                                   else serve.STATUS_OK)
            if expired:
                assert len(PROMPTS[1]) < len(got) \
                    < len(PROMPTS[1]) + budgets[1]
            else:
                assert len(got) == len(PROMPTS[i]) + budgets[i]
            np.testing.assert_array_equal(got, want[i][:len(got)])
        # the mechanism did engage: decode-only steps that sent nothing
        log = [r for r in profiling.serve_step_log()
               if r["engine"] == eng.stats.engine and r["active"]]
        assert sum(r["decode_uploads"] == 0 for r in log) >= 3
        assert any(r["decode_uploads"] for r in log[1:])


class TestBlockManager:
    def test_alloc_release_cache_eviction(self):
        from mpi4torch_tpu.serve import BlockManager

        m = BlockManager(4, 2)
        a = m.alloc(2)
        assert m.blocks_in_use == 2 and m.free_blocks == 2
        # Register then release: pages park CACHED, not freed.
        m.register(np.array([1, 2, 3]), a, 3)
        m.release(a)
        assert m.blocks_in_use == 0 and m.cached_blocks == 2
        # A full-pool alloc reclaims them LRU (index entries dropped).
        b = m.alloc(4)
        assert b is not None and m.cached_blocks == 0
        assert m.match(np.array([1, 2, 3]), 2) == ([], 0)
        assert m.alloc(1) is None
        for x in b:
            m.release([x])
        assert m.free_blocks == 4

    def test_match_caps_below_limit_and_checks_content(self):
        from mpi4torch_tpu.serve import BlockManager

        m = BlockManager(8, 2)
        toks = np.array([5, 6, 7, 8, 9])
        ids = m.alloc(3)
        m.register(toks, ids, 5)
        # Full chain + partial tail, capped at limit.
        got_ids, n = m.match(toks, 4)
        assert n == 4 and got_ids == ids[:2]
        got_ids, n = m.match(toks, 5)
        assert n == 5 and got_ids == ids
        # Diverging content does not match past the divergence.
        other = np.array([5, 6, 99, 8, 9])
        got_ids, n = m.match(other, 5)
        assert n == 2 and got_ids == ids[:1]

    def test_release_unreferenced_raises(self):
        from mpi4torch_tpu.serve import BlockManager

        m = BlockManager(2, 2)
        a = m.alloc(1)
        m.release(a)
        with pytest.raises(ValueError, match="unreferenced"):
            m.release(a)

    def test_window_class_allocates_releases_and_runs_dry(self):
        """The window class is a population of its own behind the one
        manager: pages in use or free, never cached, a capacity of its
        own, and the full class untouched by what happens to it."""
        from mpi4torch_tpu.serve import BlockManager
        from mpi4torch_tpu.serve.paging import WindowBlocks

        mgr = BlockManager(10, 8, prefix_cache=False, window=16,
                           window_blocks=4)
        w = mgr.window
        assert isinstance(w, WindowBlocks) and w.window is None
        assert (w.num_blocks, w.span, w.pages_a_slot) == (4, 16, 3)
        assert BlockManager(10, 8).window is None
        got = w.alloc(3)
        assert sorted(got) == [0, 1, 2] and w.blocks_in_use == 3
        assert w.alloc(2) is None and w.free_blocks == 1
        w.release(got[:1])
        assert w.blocks_in_use == 2 and w.cached_blocks == 0
        # Released pages go to the END of the free list: the next
        # allocation hands out the spare one first, the released after.
        assert w.alloc(2) == [3, got[0]] and w.free_blocks == 0
        assert mgr.blocks_in_use == 0 and mgr.free_blocks == 10
        # Nothing of the class is indexed: a registration is a no-op.
        w.register(np.arange(8), got[1:2], 8)
        w.release(got[1:2])
        assert w.cached_blocks == 0 and w.match(np.arange(8), 7) == ([], 0)
        with pytest.raises(ValueError, match="unreferenced"):
            w.release(got[1:2])
        with pytest.raises(ValueError, match="window must be"):
            WindowBlocks(4, 8, 0)

    def test_window_first_page_is_the_reads_first_page(self):
        """``first_page`` is the first page a query reads (positions
        ``pos - 15 .. pos`` at a window of 16), the same arithmetic as
        the read's own ``_page_span``."""
        from mpi4torch_tpu.ops.paged_attention import _page_span
        from mpi4torch_tpu.serve.paging import WindowBlocks

        w = WindowBlocks(4, 8, 16)
        assert [w.first_page(p) for p in (0, 15, 16, 22, 23, 24, 31)] \
            == [0, 0, 0, 0, 1, 1, 2]
        pos = jnp.arange(200, dtype=jnp.int32)
        first, _ = _page_span(pos, 8, 25, 16)
        assert [w.first_page(p) for p in range(200)] \
            == [int(f) for f in first]

    @pytest.mark.parametrize("window,bs,most", [
        (2048, 128, 17), (16, 8, 3), (4096, 128, 33), (1, 8, 1)])
    def test_window_pages_a_slot(self, window, bs, most):
        """What a window of positions touches at most, against a count
        over every alignment."""
        from mpi4torch_tpu.serve.paging import WindowBlocks

        w = WindowBlocks(1, bs, window)
        seen = max(p // bs - w.first_page(p) + 1
                   for p in range(2 * window + 4 * bs))
        assert w.pages_a_slot == most == seen
