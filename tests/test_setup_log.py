"""The set-up log inside the program (ISSUE 50).

``utils/profiling.py``'s one monitoring listener turns every trace,
lowering and backend compilation JAX makes into a record on the
process-wide compile log, with the program's name, the persistent
cache's answer and the innermost ``ServeStats.span`` open on the
compiling thread; a span that closes while no step is open goes onto
the set-up list and into the phase totals, and ``Engine.__init__`` is
such spans from its first line.  Held here on the CPU: the records a
jitted function's first call leaves (kinds, name, order, clock), the
cache's ``miss`` / ``hit`` / ``off``, the span and ``rid`` a record
names with and without an open step and the step record's view of it,
the constructor's spans over a lazy ``blocks`` iterable, both rings'
bounds against their totals, and that no compiled program's text moved.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import monitoring

from mpi4torch_tpu import serve
from mpi4torch_tpu.models import transformer as T
from mpi4torch_tpu.serve import engine as E
from mpi4torch_tpu.utils import profiling as P

CFG = T.TransformerConfig(vocab=37, d_model=16, n_heads=4, n_layers=3,
                          d_ff=32, max_seq=40)
KINDS = ["trace", "lower", "compile"]


@pytest.fixture(scope="module")
def params():
    return T.init_transformer(jax.random.PRNGKey(0), CFG,
                              dtype=jnp.float64)


@pytest.fixture(autouse=True)
def _clean_logs():
    P.reset_serve_stats()
    yield
    P.reset_serve_stats()


def lazy(params, taken=None):
    """``params`` with its layers made as they are asked for."""
    def blocks():
        for i, blk in enumerate(params["blocks"]):
            if taken is not None:
                taken.append(i)
            yield jax.tree.map(jnp.copy, blk)
    return {**params, "blocks": blocks()}


def of(program, log=None):
    return [r for r in (P.compile_log() if log is None else log)
            if r["program"] in (program, f"jit({program})")]


def fresh(name, k=3.0):
    """A function JAX has not met: its first call traces, lowers and
    compiles."""
    def fn(x):
        return x * k + 1
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


class TestCompileLog:
    def test_a_first_call_leaves_three_records(self):
        t_before = time.perf_counter_ns()
        fn = fresh("setup_log_probe_a")
        fn(np.arange(3.0)).block_until_ready()
        t_after = time.perf_counter_ns()
        recs = of("setup_log_probe_a")
        assert [r["kind"] for r in recs] == KINDS
        assert [r["program"] for r in recs] == [
            "setup_log_probe_a", "jit(setup_log_probe_a)",
            "jit(setup_log_probe_a)"]
        # Ordered, disjoint, and on perf_counter_ns between the calls.
        edges = [t for r in recs for t in (r["t0_ns"], r["t1_ns"])]
        assert edges == sorted(edges)
        assert t_before <= edges[0] and edges[-1] <= t_after
        for r in recs:
            assert r["span"] is None and r["rid"] is None
            assert r["thread"] == threading.get_ident()
            assert ("cache" in r) == (r["kind"] == "compile")
        # The second call is no event.
        n = len(P.compile_log())
        fn(np.arange(3.0)).block_until_ready()
        assert len(P.compile_log()) == n

    def test_an_inner_trace_is_part_of_the_outer_ones(self):
        """A function traced inside another's trace is counted, not
        recorded: the program's record covers it."""
        inner = fresh("setup_log_probe_inner")

        def outer(x):
            return inner(x) + 2
        outer.__name__ = outer.__qualname__ = "setup_log_probe_outer"
        jax.jit(outer)(np.arange(4.0)).block_until_ready()
        assert not of("setup_log_probe_inner")
        assert [r["kind"] for r in of("setup_log_probe_outer")] == KINDS
        nested = P.compile_totals()[("trace.inner", None)]
        # inner, and jnp's own inside it (whose seconds are inner's too)
        assert nested["count"] >= 2 and nested["seconds"] > 0

    def test_totals_count_every_record(self):
        fresh("setup_log_probe_b")(np.arange(3.0)).block_until_ready()
        log, totals = P.compile_log(), P.compile_totals()
        totals.pop(("trace.inner", None), None)
        assert sum(t["count"] for t in totals.values()) == len(log)
        for (kind, cache), tot in totals.items():
            mine = [r for r in log
                    if (r["kind"], r.get("cache")) == (kind, cache)]
            assert tot["count"] == len(mine)
            assert tot["seconds"] == pytest.approx(
                sum(r["t1_ns"] - r["t0_ns"] for r in mine) / 1e9)
        assert {k for k, _ in totals} == set(KINDS)

    def test_the_ring_drops_oldest_first_and_the_totals_do_not(
            self, monkeypatch):
        from collections import deque

        monkeypatch.setattr(P, "_COMPILE_LOG", deque(maxlen=4))
        for k in range(3):
            fresh(f"setup_log_probe_ring{k}")(np.arange(2.0))
        log = P.compile_log()
        assert len(log) == 4 == P._COMPILE_LOG.maxlen
        assert [r["t1_ns"] for r in log] == sorted(r["t1_ns"] for r in log)
        assert not of("setup_log_probe_ring0", log)
        assert [r["kind"] for r in of("setup_log_probe_ring2", log)] == KINDS
        assert sum(t["count"] for t in P.compile_totals().values()) >= 9

    def test_the_cap_is_the_rings(self):
        assert P._COMPILE_LOG.maxlen == P.COMPILE_LOG_CAP
        assert P._SETUP_SPANS.maxlen == P.SETUP_SPAN_CAP

    def test_reset_empties_both_logs(self):
        stats = P.ServeStats()
        with stats.span(E.SPAN_POOL):
            fresh("setup_log_probe_c")(np.arange(3.0))
        assert P.compile_log() and P.setup_spans() and P.compile_totals()
        P.reset_serve_stats()
        assert (P.compile_log(), P.setup_spans(), P.compile_totals()) \
            == ([], [], {})


@pytest.fixture
def cache_dir(tmp_path):
    """The persistent compilation cache at a directory of the test's,
    every program kept; what was configured comes back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path), True, 0.0, 0)):
        jax.config.update(k, v)
    cc.reset_cache()
    yield tmp_path
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


class TestCacheOutcome:
    @staticmethod
    def compiled(fn):
        fn(np.arange(5.0)).block_until_ready()
        return [r for r in of(fn.__wrapped__.__name__)
                if r["kind"] == "compile"][-1]

    def test_miss_then_hit(self, cache_dir):
        first = self.compiled(fresh("setup_log_probe_cached", 7.0))
        assert first["cache"] == "miss" and first["retrieval_s"] is None
        # The same program again, the in-memory caches gone: the
        # executable comes back from the directory.
        jax.clear_caches()
        P.reset_serve_stats()
        second = self.compiled(fresh("setup_log_probe_cached", 7.0))
        assert second["cache"] == "hit"
        assert 0 < second["retrieval_s"] <= \
            (second["t1_ns"] - second["t0_ns"]) / 1e9
        totals = P.compile_totals()
        assert totals[("compile", "hit")]["count"] >= 1

    def test_off_without_a_cache(self, cache_dir):
        from jax.experimental.compilation_cache import compilation_cache as cc

        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        rec = self.compiled(fresh("setup_log_probe_uncached", 9.0))
        assert rec["cache"] == "off" and rec["retrieval_s"] is None

    def test_an_answer_is_for_one_compilation(self, cache_dir):
        """A hit's answer does not leak onto the next program, compiled
        with the cache off."""
        from jax.experimental.compilation_cache import compilation_cache as cc

        self.compiled(fresh("setup_log_probe_leak", 11.0))
        jax.clear_caches()
        assert self.compiled(
            fresh("setup_log_probe_leak", 11.0))["cache"] == "hit"
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        assert self.compiled(
            fresh("setup_log_probe_leak2", 13.0))["cache"] == "off"


class TestSpanNamed:
    def test_outside_a_step(self):
        """A span with no step open: the record names it, the span is a
        set-up span, and no step record knows of either."""
        stats = P.ServeStats()
        with stats.span(E.SPAN_CONSTRUCT):
            with stats.span(E.SPAN_SHARD_LAYER, 5):
                fresh("setup_log_probe_d")(np.arange(3.0))
            fresh("setup_log_probe_e")(np.arange(3.0))
        fresh("setup_log_probe_f")(np.arange(3.0))
        assert {(r["span"], r["rid"]) for r in of("setup_log_probe_d")} \
            == {(E.SPAN_SHARD_LAYER, 5)}
        assert {(r["span"], r["rid"]) for r in of("setup_log_probe_e")} \
            == {(E.SPAN_CONSTRUCT, None)}
        assert {r["span"] for r in of("setup_log_probe_f")} == {None}
        spans = P.setup_spans()
        assert [(s[0], s[3], s[4]) for s in spans] == [
            (E.SPAN_SHARD_LAYER, 5, stats.engine),
            (E.SPAN_CONSTRUCT, None, stats.engine)]
        (layer, whole) = spans
        assert whole[1] <= layer[1] <= layer[2] <= whole[2]
        for r in of("setup_log_probe_d"):
            assert layer[1] <= r["t0_ns"] <= r["t1_ns"] <= layer[2]
        assert P.serve_step_log() == []
        assert stats.counters["step_compiles"] == 0
        phases = stats.snapshot()["phase_s"]
        assert phases[E.SPAN_CONSTRUCT] == {
            "seconds": (whole[2] - whole[1]) / 1e9, "count": 1}
        assert serve.stats()["phase_s"] == {}     # not a registered engine

    def test_inside_a_step_it_is_also_the_steps(self):
        """One source, two views: the step record's ``compiles`` is the
        compile log's record, with the event's own seconds."""
        seen = []

        def listen(event, seconds, **_):
            if event == P._COMPILE_EVENT:
                seen.append(seconds)
        monitoring.register_event_duration_secs_listener(listen)
        try:
            stats = P.ServeStats()
            with stats.span(P.STEP_SPAN):
                with stats.span(E.SPAN_PREFILL, "r-3"):
                    fresh("setup_log_probe_g")(np.arange(3.0))
        finally:
            monitoring.unregister_event_duration_listener(listen)
        (rec,) = P.serve_step_log()
        (span, rid, seconds), = rec["compiles"]
        (mine,) = [r for r in of("setup_log_probe_g")
                   if r["kind"] == "compile"]
        assert (span, rid) == (mine["span"], mine["rid"]) \
            == (E.SPAN_PREFILL, "r-3")
        assert [seconds] == seen
        assert (mine["t1_ns"] - mine["t0_ns"]) / 1e9 \
            == pytest.approx(seconds, abs=1e-9)
        assert rec["step_compiles"] == 1
        # The trace and the lowering are on the log alone.
        assert {r["span"] for r in of("setup_log_probe_g")} \
            == {E.SPAN_PREFILL}
        assert P.setup_spans() == []

    def test_each_thread_has_its_own_innermost_span(self):
        stats, other = P.ServeStats(), P.ServeStats()

        def elsewhere():
            with other.span(E.SPAN_POOL):
                fresh("setup_log_probe_h")(np.arange(3.0))
        with stats.span(E.SPAN_CONSTRUCT):
            t = threading.Thread(target=elsewhere)
            t.start()
            t.join(60)
            assert not t.is_alive()
            fresh("setup_log_probe_i")(np.arange(3.0))
        assert {r["span"] for r in of("setup_log_probe_h")} == {E.SPAN_POOL}
        assert {r["span"] for r in of("setup_log_probe_i")} \
            == {E.SPAN_CONSTRUCT}
        assert {r["thread"] for r in of("setup_log_probe_h")} \
            != {r["thread"] for r in of("setup_log_probe_i")}


def spans_of(eng):
    return [s for s in P.setup_spans() if s[4] == eng.stats.engine]


def covered(parent, children) -> float:
    """The share of ``parent``'s interval that ``children`` cover
    (siblings: disjoint)."""
    return sum(c[2] - c[1] for c in children) / (parent[2] - parent[1])


@pytest.mark.parametrize("spmd", [False, True], ids=["eager", "spmd"])
class TestConstructorSpans:
    def test_a_lazy_iterable_is_taken_and_sharded_a_layer_at_a_time(
            self, params, spmd):
        taken = []
        eng = serve.Engine(CFG, lazy(params, taken),
                           serve.ServeConfig(slots=2, max_new=3,
                                             block_size=4),
                           spmd=spmd, nranks=2 if spmd else None)
        assert taken == list(range(CFG.n_layers))
        spans = spans_of(eng)
        by = {}
        for s in spans:
            by.setdefault(s[0], []).append(s)
        assert set(by) == {E.SPAN_CONSTRUCT, E.SPAN_SHARD, E.SPAN_SHARD_TOP,
                           E.SPAN_SHARD_TAKE, E.SPAN_SHARD_LAYER,
                           E.SPAN_POOL, E.SPAN_BUILD_INSTALL}
        # Once a layer, with the layer's index; the iterable is asked
        # once more, and says it is done.
        assert [s[3] for s in by[E.SPAN_SHARD_LAYER]] \
            == list(range(CFG.n_layers))
        assert [s[3] for s in by[E.SPAN_SHARD_TAKE]] \
            == list(range(CFG.n_layers + 1))
        for name in (E.SPAN_CONSTRUCT, E.SPAN_SHARD, E.SPAN_SHARD_TOP,
                     E.SPAN_POOL, E.SPAN_BUILD_INSTALL):
            assert len(by[name]) == 1, name
        # take(i) ends before layer(i) begins, which ends before
        # take(i + 1).
        walk = sorted(by[E.SPAN_SHARD_TAKE] + by[E.SPAN_SHARD_LAYER],
                      key=lambda s: s[1])
        assert [s[0] for s in walk] == (
            [E.SPAN_SHARD_TAKE, E.SPAN_SHARD_LAYER] * CFG.n_layers
            + [E.SPAN_SHARD_TAKE])
        assert all(a[2] <= b[1] for a, b in zip(walk, walk[1:]))
        # Children lie inside their parents and cover them: the shard
        # to within a tenth, the constructor to within half on an engine
        # this small (the rest is the block manager and the shapes).
        (whole,), (shard,) = by[E.SPAN_CONSTRUCT], by[E.SPAN_SHARD]
        inner = by[E.SPAN_SHARD_TOP] + walk
        outer = [shard] + by[E.SPAN_POOL] + by[E.SPAN_BUILD_INSTALL]
        assert all(shard[1] <= s[1] <= s[2] <= shard[2] for s in inner)
        assert all(whole[1] <= s[1] <= s[2] <= whole[2] for s in outer)
        assert 0.9 <= covered(shard, inner) <= 1.0
        assert 0.5 <= covered(whole, outer) <= 1.0

    def test_the_constructors_seconds_are_in_the_phase_totals(
            self, params, spmd):
        eng = serve.Engine(CFG, params, serve.ServeConfig(slots=2, max_new=3),
                           spmd=spmd, nranks=2 if spmd else None)
        (whole,) = [s for s in spans_of(eng) if s[0] == E.SPAN_CONSTRUCT]
        assert eng.stats.snapshot()["phase_s"][E.SPAN_CONSTRUCT] == {
            "seconds": (whole[2] - whole[1]) / 1e9, "count": 1}
        assert serve.stats()["phase_s"][E.SPAN_SHARD_LAYER]["count"] \
            == CFG.n_layers
        # What the constructor compiled names the span it compiled in,
        # and no step: the first step's record has its own alone.
        made = [r for r in P.compile_log()
                if (r["span"] or "").startswith(E.SPAN_CONSTRUCT)]
        assert made and all(
            whole[1] <= r["t1_ns"] <= whole[2] for r in made)
        eng.submit(np.arange(1, 6))
        eng.step()
        (rec,) = [r for r in P.serve_step_log()
                  if r["engine"] == eng.stats.engine]
        in_step = [r for r in P.compile_log() if r["kind"] == "compile"
                   and (r["span"] or "").startswith(P.STEP_SPAN)]
        assert [(r["span"], r["rid"]) for r in in_step] \
            == [c[:2] for c in rec.get("compiles", [])]
        assert len(in_step) == rec["step_compiles"]

    def test_a_constructor_that_raises_still_closes_its_span(
            self, params, spmd):
        with pytest.raises(ValueError, match="n_layers"):
            serve.Engine(CFG, {**params, "blocks": params["blocks"][:1]},
                         serve.ServeConfig(slots=2, max_new=3),
                         spmd=spmd, nranks=2 if spmd else None)
        assert [s[0] for s in P.setup_spans()][-2:] \
            == [E.SPAN_SHARD, E.SPAN_CONSTRUCT]
        assert P._STEPPING.stack == []


def test_program_texts_compiles_under_its_own_span(params):
    eng = serve.Engine(CFG, params, serve.ServeConfig(slots=2, max_new=3),
                       spmd=True, nranks=2)
    eng.submit(np.arange(1, 6))
    eng.run()
    n = len(P.compile_log())
    texts = eng.program_texts()
    assert set(texts) == {"decode", "prefill.5"}
    new = P.compile_log()[n:]
    assert new and {r["span"] for r in new} == {E.SPAN_PROGRAM_TEXTS}
    assert [s[0] for s in spans_of(eng)][-1] == E.SPAN_PROGRAM_TEXTS


def test_the_step_text_is_the_same_without_the_listeners(params):
    """No span enters traced code and the listeners only listen: the
    decode step lowers to the same text with them and without."""
    def text():
        eng = serve.Engine(CFG, params,
                           serve.ServeConfig(slots=2, max_new=3,
                                             block_size=4),
                           spmd=True, nranks=2)
        return eng.lower_step().as_text()

    with_log = text()
    assert P.compile_log()
    monitoring.unregister_event_duration_listener(P._on_compile)
    monitoring.unregister_event_listener(P._on_cache_event)
    try:
        P.reset_serve_stats()
        without = text()
        assert P.compile_log() == []
    finally:
        monitoring.register_event_duration_secs_listener(P._on_compile)
        monitoring.register_event_listener(P._on_cache_event)
    assert with_log == without


def test_a_record_costs_microseconds():
    """The always-on promise: what the listener does with one event
    costs microseconds (the count a process is in PERF.md)."""
    n = 5_000
    t0 = time.perf_counter()
    for _ in range(n):
        P._on_compile(P._COMPILE_EVENT, 0.001, fun_name="jit(f)")
    assert (time.perf_counter() - t0) / n < 50e-6
