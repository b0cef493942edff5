"""The spans and the step log inside ``Engine.step()`` (ISSUE 25).

``ServeStats.span`` lays the phases of every step on
``time.perf_counter_ns()`` and, through ``jax.profiler.
TraceAnnotation``, on any profiler capture; a closed step goes onto the
process-wide step log.  Held here, on tiny paged and dense engines in
both execution modes: the shape of a step's spans (nesting, which
phases an admitting and a decode-only step have, the request id they
carry, one span per phase however many pages a prompt has), the
``install_writes`` census, the log's lifetime (capped, outlives its
engine, emptied by ``reset_serve_stats()``), ``snapshot()["phase_s"]``
against the log, the annotation in a CPU profiler capture, and that
the served tokens are still ``generate()``'s.
"""

import gc
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4torch_tpu import serve
from mpi4torch_tpu.models import transformer as T
from mpi4torch_tpu.serve import engine as E
from mpi4torch_tpu.utils import profiling as P

CFG = T.TransformerConfig(vocab=37, d_model=16, n_heads=4, n_layers=2,
                          d_ff=32, max_seq=40)
BLOCK = 4

ADMIT_PHASES = [E.SPAN_PLAN, E.SPAN_PREFILL, E.SPAN_INSTALL,
                E.SPAN_FIRST_TOKEN]
DECODE_PHASES = [E.SPAN_DISPATCH, E.SPAN_FETCH, E.SPAN_SELECT]
# A decode step's spans in the order they close: children first.
DECODE_SPANS = [E.SPAN_DISPATCH_INPUTS, E.SPAN_DISPATCH_CALL,
                E.SPAN_DISPATCH, E.SPAN_FETCH_TOKENS,
                E.SPAN_FETCH_COUNTERS, E.SPAN_FETCH, E.SPAN_SELECT]

# (paged, spmd): every engine the spans must read the same on.
ENGINES = [pytest.param(False, False, id="dense-eager"),
           pytest.param(False, True, id="dense-spmd"),
           pytest.param(True, False, id="paged-eager"),
           pytest.param(True, True, id="paged-spmd")]


@pytest.fixture(scope="module")
def params():
    return T.init_transformer(jax.random.PRNGKey(0), CFG,
                              dtype=jnp.float64)


@pytest.fixture(autouse=True)
def _clean_log():
    P.reset_serve_stats()
    yield
    P.reset_serve_stats()


def make_engine(params, paged, spmd, **kw):
    if paged:
        kw.setdefault("block_size", BLOCK)
    return serve.Engine(CFG, params,
                        serve.ServeConfig(slots=2, max_new=3, **kw),
                        spmd=spmd, nranks=2 if spmd else None)


def names(record):
    return [s[0] for s in record["spans"]]


def log_of(eng):
    return [r for r in P.serve_step_log()
            if r["engine"] == eng.stats.engine]


@pytest.mark.parametrize("paged,spmd", ENGINES)
class TestStepSpans:
    def test_children_nest_inside_the_step(self, params, paged, spmd):
        eng = make_engine(params, paged, spmd)
        eng.submit(np.arange(1, 6))
        eng.submit(np.arange(7, 10))
        eng.run()
        log = log_of(eng)
        assert len(log) >= 2
        for rec in log:
            *children, step = rec["spans"]
            assert step[0] == P.STEP_SPAN
            assert (step[1], step[2]) == (rec["t0_ns"], rec["t1_ns"])
            for name, t0, t1, _ in children:
                assert name.startswith(P.STEP_SPAN + ".")
                assert step[1] <= t0 <= t1 <= step[2]
            # Any two spans are disjoint or nested: siblings never
            # overlap, and a child lies inside its parent.
            for i, (_, a0, a1, _) in enumerate(children):
                for _, b0, b1, _ in children[i + 1:]:
                    assert (a1 <= b0 or b1 <= a0
                            or (a0 <= b0 and b1 <= a1)
                            or (b0 <= a0 and a1 <= b1))
            # The admission phases lie inside the admit span.
            (admit,) = [s for s in children if s[0] == E.SPAN_ADMIT]
            for name, t0, t1, _ in children:
                if name.startswith(E.SPAN_ADMIT + "."):
                    assert admit[1] <= t0 <= t1 <= admit[2]

    def test_admitting_step_carries_the_rid(self, params, paged, spmd):
        eng = make_engine(params, paged, spmd)
        rid = eng.submit(np.arange(1, 6), rid="r-17")
        eng.step()
        (rec,) = log_of(eng)
        for phase in ADMIT_PHASES:
            (span,) = [s for s in rec["spans"] if s[0] == phase]
            assert span[3] == rid
        assert names(rec).count(E.SPAN_EXPIRE) == 1
        for phase in DECODE_PHASES:     # admitted, then decoded
            (span,) = [s for s in rec["spans"] if s[0] == phase]
            assert span[3] is None
        assert rec["admitted"] == 1 and rec["active"] == 1
        assert rec["prefill_tokens"] == 5

    def test_decode_only_step(self, params, paged, spmd):
        eng = make_engine(params, paged, spmd)
        eng.submit(np.arange(1, 6))
        eng.step()
        eng.step()
        rec = log_of(eng)[-1]
        assert names(rec) == [E.SPAN_EXPIRE, E.SPAN_ADMIT,
                              *DECODE_SPANS, P.STEP_SPAN]
        assert (rec["admitted"], rec["prefill_tokens"],
                rec["install_writes"], rec["active"]) == (0, 0, 0, 1)

    def test_dispatch_and_fetch_split_where_their_work_happens(
            self, params, paged, spmd):
        """``dispatch`` = ``inputs`` then ``call``, ``fetch`` =
        ``tokens`` then ``counters``: each child inside its parent, in
        that order, and the two together covering the parent to within
        the spans' own cost."""
        eng = make_engine(params, paged, spmd)
        for n in (5, 3, 6):
            eng.submit(np.arange(1, 1 + n))
        eng.run()
        decoded = [r for r in log_of(eng) if r["active"]]
        assert len(decoded) >= 3
        for rec in decoded:
            at = {s[0]: s for s in rec["spans"]}
            for parent, first, second in (
                    (E.SPAN_DISPATCH, E.SPAN_DISPATCH_INPUTS,
                     E.SPAN_DISPATCH_CALL),
                    (E.SPAN_FETCH, E.SPAN_FETCH_TOKENS,
                     E.SPAN_FETCH_COUNTERS)):
                for name in (parent, first, second):
                    assert names(rec).count(name) == 1
                _, p0, p1, _ = at[parent]
                _, a0, a1, _ = at[first]
                _, b0, b1, _ = at[second]
                assert p0 <= a0 <= a1 <= b0 <= b1 <= p1
                bare = (p1 - p0) - (a1 - a0) - (b1 - b0)
                assert 0 <= bare < 200_000        # ns: 3 spans' cost

    def test_decode_uploads_counts_the_transfers_made(
            self, params, paged, spmd, monkeypatch):
        """``decode_uploads`` on a step's record is the arrays of the
        slot state ``_step_inputs`` sent up in it, those in which the
        host's state differs from what the device holds: after an
        admission what the admission wrote (tokens, positions, live
        mask, the table, the keys where the engine samples); in a
        decode-only step behind a decode-only step nothing, and no
        transfer is made; where a slot crosses a page (never at
        ``block_size=0``: a slot's one page is its whole extent), the
        table alone."""
        for temperature, keys in ((0.0, 0), (0.7, 1)):
            eng = make_engine(params, paged, spmd, temperature=temperature)
            sent, inputs = [], eng._step_inputs

            def watched():          # count inside _step_inputs only
                put, asarray = jax.device_put, jnp.asarray

                def counting(x, *a, **kw):
                    sent[-1] += [np.shape(leaf)[1 if spmd else 0:]
                                 for leaf in jax.tree.leaves(x)]
                    return put(x, *a, **kw)

                sent.append([])
                with monkeypatch.context() as m:
                    m.setattr(E.jax, "device_put", counting)
                    m.setattr(E.jnp, "asarray", lambda x, *a, **kw: (
                        sent[-1].append(np.shape(x)),
                        asarray(x, *a, **kw))[1])
                    return inputs()

            eng._step_inputs = watched
            key = jax.random.PRNGKey(3) if keys else None
            everything = 4 + keys
            # prompt of 5 at pages of 4: the slot holds rows 0..7 and
            # crosses into its third page when it writes position 8.
            eng.submit(np.arange(1, 6), max_new=8, key=key)
            eng.step()                       # admits and decodes: pos 6
            eng.step()                       # decode-only: pos 7
            eng.submit(np.arange(1, 4), max_new=8, key=key)
            eng.step()                       # admits the second: 8 and 4
            eng.step()                       # both cross a page
            eng.step()
            recs = log_of(eng)
            table = (2, CFG.max_seq // BLOCK)
            want = [everything, 0, everything, 1 if paged else 0, 0]
            assert [r["decode_uploads"] for r in recs] == want
            assert [len(shapes) for shapes in sent] == want
            assert sent[3] == ([table] if paged else [])
            # An upload is what the step's own output was, to the
            # compiled step: neither retraces it.
            if spmd:
                assert recs[1]["step_compiles"] == 0
            assert [r["step_compiles"] for r in recs[3:]] == [0, 0]
        idle = make_engine(params, paged, spmd)
        idle.step()
        assert log_of(idle)[-1]["decode_uploads"] == 0

    def test_no_select_sync_in_any_decode_step(self, params, paged, spmd):
        """Every step that decoded holds its dispatch, fetch and select
        spans, and ``decode.select`` made no round trip to the device:
        the step chose the tokens."""
        eng = make_engine(params, paged, spmd)
        for n in (5, 3, 6):
            eng.submit(np.arange(1, 1 + n))
        eng.run()
        decoded = [r for r in log_of(eng) if r["active"]]
        assert len(decoded) >= 3
        for rec in decoded:
            assert rec["decode_select_syncs"] == 0
            for phase in DECODE_PHASES:
                assert names(rec).count(phase) == 1
        assert eng.stats.counters["decode_select_syncs"] == 0
        assert eng.stats.counters["decode_tokens"] \
            == sum(r["active"] for r in decoded)
        # A row of logits handed to _select inside decode.select is a
        # round trip, and is counted as one.
        eng.submit(np.arange(1, 6))
        eng.step()
        select = eng._select
        eng._select = lambda req, tok: select(
            req, np.eye(CFG.vocab)[int(tok)])
        eng.step()
        assert log_of(eng)[-1]["decode_select_syncs"] == 1

    def test_idle_step_has_no_decode_span(self, params, paged, spmd):
        eng = make_engine(params, paged, spmd)
        eng.step()
        (rec,) = log_of(eng)
        assert names(rec) == [E.SPAN_EXPIRE, E.SPAN_ADMIT, P.STEP_SPAN]
        assert rec["active"] == 0

    def test_span_count_does_not_grow_with_pages(self, params, paged,
                                                 spmd):
        counts, writes = [], []
        for n_tokens in (BLOCK, 8 * BLOCK):       # 1 page, 8 pages
            eng = make_engine(params, paged, spmd)
            eng.submit(np.arange(n_tokens) % CFG.vocab)
            eng.step()
            (rec,) = log_of(eng)
            counts.append(len(rec["spans"]))
            writes.append(rec["install_writes"])
        assert counts[0] == counts[1] == 10 + 4
        # install_writes: one dispatch an install, however many pages
        # or cache leaves.
        assert writes == [1, 1]

    def test_phase_totals_sum_the_log(self, params, paged, spmd):
        eng = make_engine(params, paged, spmd)
        for n in (5, 3, 6):
            eng.submit(np.arange(1, 1 + n))
        eng.run()
        want = {}
        # Since ISSUE 50 the spans closed outside a step (the
        # constructor's) count into the phase totals beside the log's.
        outside = [s[:4] for s in P.setup_spans()
                   if s[4] == eng.stats.engine]
        assert outside
        for spans in [outside] + [rec["spans"] for rec in log_of(eng)]:
            for name, t0, t1, _ in spans:
                ns, count = want.get(name, (0, 0))
                want[name] = (ns + t1 - t0, count + 1)
        got = eng.stats.snapshot()["phase_s"]
        assert set(got) == set(want)
        for name, (ns, count) in want.items():
            assert got[name]["count"] == count
            assert got[name]["seconds"] == pytest.approx(ns / 1e9)
        assert got[P.STEP_SPAN]["count"] == len(log_of(eng))
        agg = serve.stats()["phase_s"]
        assert agg[P.STEP_SPAN] == got[P.STEP_SPAN]

    def test_tokens_still_match_generate(self, params, paged, spmd):
        eng = make_engine(params, paged, spmd)
        prompts = [np.arange(1, 6), np.arange(7, 10), np.arange(20, 29)]
        rids = [eng.submit(p) for p in prompts]
        out = eng.run()
        for p, rid in zip(prompts, rids):
            ref = T.generate(CFG, params,
                             jnp.asarray(p, jnp.int32)[None, :], 3,
                             dtype=jnp.float64)
            np.testing.assert_array_equal(out[rid], np.asarray(ref[0]))


@pytest.mark.parametrize("paged,spmd", ENGINES)
def test_a_wrapped_select_feeds_the_next_step(params, paged, spmd):
    """``_select`` is the one hand-over of every emitted token: where an
    instance wraps it and hands on another token than the step chose
    (the benchmark's broken-path control), the host's token differs
    from the device's and goes up before the next step, which decodes
    from it as it did when every step uploaded everything."""
    def served(wrapped, forgetful):
        eng = make_engine(params, paged, spmd)
        if wrapped:
            select = eng._select
            eng._select = lambda req, choice: (select(req, choice) + 1) \
                % CFG.vocab
        rids = [eng.submit(np.arange(1, 6), max_new=6),
                eng.submit(np.arange(7, 10), max_new=6)]
        while eng.pending():
            if forgetful:           # the parent's rule: all of it, every step
                eng._held.clear()
            eng.step()
        out = eng.results()
        return [out[r] for r in rids], log_of(eng)

    kept, log = served(True, False)
    all_up, every = served(True, True)
    plain, _ = served(False, False)
    for a, b, c in zip(kept, all_up, plain):
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a, c)
    everything = 4
    assert [r["decode_uploads"] for r in every] \
        == [everything] * len(every)
    # Behind the admitting step the tokens alone go up, and the table
    # with them in the two steps in which a slot crosses a page (the
    # short prompt into its second, the long one into its third).
    later = [r["decode_uploads"] for r in log[1:]]
    assert len(later) == 4 and set(later) <= {1, 2}
    assert later.count(2) == (2 if paged else 0)


@pytest.mark.parametrize("paged,spmd", ENGINES)
def test_a_new_prompt_length_names_the_step_that_compiled(params, paged,
                                                          spmd):
    """A step that meets a prompt length for the first time compiles
    inside ``admit.prefill``: its record says so, with the request; the
    same length again compiles nothing.  (An eager engine compiles op
    by op and shares JAX's cache with every earlier test, so only a
    compiled engine is certain to compile here.)"""
    n = 11                          # a length no other case prefills
    eng = make_engine(params, paged, spmd)
    eng.submit(np.arange(n) % CFG.vocab, rid="first")
    eng.step()
    (first,) = log_of(eng)
    if spmd:
        assert first["step_compiles"] >= 1
    assert len(first.get("compiles", [])) == first["step_compiles"]
    for name, rid, seconds in first.get("compiles", []):
        assert name.startswith(P.STEP_SPAN + ".") and seconds > 0
        # Only a span of the admission carries the request.
        assert rid == ("first" if name in ADMIT_PHASES else None)
    if spmd:
        assert (E.SPAN_PREFILL, "first") in [
            c[:2] for c in first["compiles"]]
    eng.run()
    eng.submit(np.arange(1, 1 + n) % CFG.vocab, rid="again")
    eng.step()
    again = log_of(eng)[-1]
    assert again["admitted"] == 1 and again["prefill_tokens"] == n
    assert again["step_compiles"] == 0 and "compiles" not in again
    assert eng.stats.counters["step_compiles"] \
        == sum(r["step_compiles"] for r in log_of(eng))
    assert serve.stats()["step_compiles"] \
        == eng.stats.counters["step_compiles"]
    P.reset_serve_stats()
    assert eng.stats.counters["step_compiles"] == 0


class TestCompileListener:
    """``profiling._on_compile``: a backend compilation that ends while
    a step is open goes onto that step's record with the innermost span
    open on the compiling thread, and into ``step_compiles``."""

    @staticmethod
    def compile_something(k):
        jax.jit(lambda x: x * k + 1)(np.arange(3.0)).block_until_ready()

    def test_named_by_the_innermost_open_span(self):
        stats = P.ServeStats()
        with stats.span(P.STEP_SPAN):
            with stats.span(E.SPAN_ADMIT):
                with stats.span(E.SPAN_PREFILL, "r-9"):
                    self.compile_something(3)
                self.compile_something(5)
            with stats.span(E.SPAN_DISPATCH):
                with stats.span(E.SPAN_DISPATCH_CALL):
                    self.compile_something(7)
        (rec,) = P.serve_step_log()
        assert [c[:2] for c in rec["compiles"]] == [
            (E.SPAN_PREFILL, "r-9"), (E.SPAN_ADMIT, None),
            (E.SPAN_DISPATCH_CALL, None)]
        assert all(c[2] > 0 for c in rec["compiles"])
        assert rec["step_compiles"] == 3 == stats.counters["step_compiles"]

    def test_a_cached_program_is_no_compilation(self):
        stats = P.ServeStats()
        fn = jax.jit(lambda x: x * 11 + 1)
        for _ in range(2):
            with stats.span(P.STEP_SPAN):
                with stats.span(E.SPAN_PREFILL):
                    fn(np.arange(3.0))
        first, second = P.serve_step_log()
        assert first["step_compiles"] == 1
        assert second["step_compiles"] == 0 and "compiles" not in second

    def test_outside_a_step_it_is_nobodys(self):
        stats = P.ServeStats()
        self.compile_something(13)
        with stats.span(E.SPAN_PREFILL):          # no step is open
            self.compile_something(17)
        with stats.span(P.STEP_SPAN):
            pass
        self.compile_something(19)                # the step has closed
        (rec,) = P.serve_step_log()
        assert rec["step_compiles"] == 0 and "compiles" not in rec
        assert stats.counters["step_compiles"] == 0

    def test_another_threads_step_is_not_charged(self):
        import threading

        stats = P.ServeStats()
        with stats.span(P.STEP_SPAN):
            t = threading.Thread(target=self.compile_something, args=(23,))
            t.start()
            t.join()
        (rec,) = P.serve_step_log()
        assert rec["step_compiles"] == 0

    def test_two_engines_on_one_thread(self):
        a, b = P.ServeStats(), P.ServeStats()
        with a.span(P.STEP_SPAN):
            self.compile_something(29)
        with b.span(P.STEP_SPAN):
            self.compile_something(31)
            self.compile_something(37)
        assert (a.counters["step_compiles"],
                b.counters["step_compiles"]) == (1, 2)


@pytest.mark.parametrize("spmd", [False, True], ids=["eager", "spmd"])
def test_chunked_prefill_spans_one_set_per_chunk(params, spmd):
    """A prompt prefilled chunk by chunk: every step that ran a chunk
    has one prefill and one install span with the request's id, the
    first token comes with the last chunk, and ``install_writes``
    counts one dispatch a chunk."""
    eng = make_engine(params, True, spmd, prefill_chunk=BLOCK)
    rid = eng.submit(np.arange(3 * BLOCK) % CFG.vocab)
    for _ in range(3):
        eng.step()
    log = log_of(eng)
    assert [names(r).count(E.SPAN_PLAN) for r in log] == [1, 0, 0]
    for rec in log:
        for phase in (E.SPAN_PREFILL, E.SPAN_INSTALL):
            (span,) = [s for s in rec["spans"] if s[0] == phase]
            assert span[3] == rid
        assert rec["prefill_tokens"] == BLOCK
        assert rec["install_writes"] == 1
    assert [names(r).count(E.SPAN_FIRST_TOKEN) for r in log] == [0, 0, 1]
    assert [r["admitted"] for r in log] == [0, 0, 1]
    assert [r["active"] for r in log] == [0, 0, 1]


def test_deferred_admission_still_plans(params):
    """A request the pool cannot hold yet is planned (and left queued)
    every step: a plan span with its id and no prefill."""
    eng = make_engine(params, True, False, num_blocks=3)
    eng.submit(np.arange(1, 9), rid="a", max_new=4)     # 2 pages + 1
    eng.submit(np.arange(11, 19), rid="b", max_new=4)
    eng.step()
    (rec,) = log_of(eng)
    plans = [s for s in rec["spans"] if s[0] == E.SPAN_PLAN]
    assert [s[3] for s in plans] == ["a", "b"]
    assert names(rec).count(E.SPAN_PREFILL) == 1
    assert rec["admitted"] == 1


class TestStepLog:
    def test_capped(self):
        assert P._STEP_LOG.maxlen == P.STEP_LOG_CAP == 8192
        stats = P.ServeStats()
        extra = 5
        for _ in range(P.STEP_LOG_CAP + extra):
            with stats.span(P.STEP_SPAN):
                pass
        log = P.serve_step_log()
        assert len(log) == P.STEP_LOG_CAP
        assert [r["t0_ns"] for r in log] == sorted(r["t0_ns"] for r in log)
        # The phase totals are never capped.
        assert stats.snapshot()["phase_s"][P.STEP_SPAN]["count"] \
            == P.STEP_LOG_CAP + extra

    def test_outlives_its_engine_and_is_reset(self, params):
        eng = make_engine(params, True, False)
        eng.submit(np.arange(1, 6))
        eng.run()
        serial, steps = eng.stats.engine, len(log_of(eng))
        del eng
        gc.collect()
        assert serve.stats()["n_engines"] == 0
        log = P.serve_step_log()
        assert len([r for r in log if r["engine"] == serial]) == steps > 0
        log.clear()                       # a copy: the ring is untouched
        assert len(P.serve_step_log()) == steps
        P.reset_serve_stats()
        assert P.serve_step_log() == []

    def test_reset_clears_the_phase_totals(self, params):
        eng = make_engine(params, False, False)
        eng.submit(np.arange(1, 6))
        eng.run()
        assert eng.stats.snapshot()["phase_s"]
        eng.stats.reset()
        assert eng.stats.snapshot()["phase_s"] == {}
        assert eng.stats.counters["install_writes"] == 0

    def test_engines_have_their_own_serials(self, params):
        a = make_engine(params, False, False)
        b = make_engine(params, False, False)
        assert a.stats.engine != b.stats.engine
        a.step()
        b.step()
        b.step()
        assert (len(log_of(a)), len(log_of(b))) == (1, 2)

    def test_span_outside_a_step_is_not_recorded(self):
        """Not on the step log, that is: since ISSUE 50 it is a set-up
        span (tests/test_setup_log.py) and counts into the phases."""
        stats = P.ServeStats()
        with stats.span(E.SPAN_FETCH):
            pass
        assert P.serve_step_log() == []
        assert stats.snapshot()["phase_s"][E.SPAN_FETCH]["count"] == 1
        assert [s[0] for s in P.setup_spans()] == [E.SPAN_FETCH]

    def test_a_step_that_raises_is_still_logged(self):
        stats = P.ServeStats()
        with pytest.raises(RuntimeError):
            with stats.span(P.STEP_SPAN):
                with stats.span(E.SPAN_FETCH):
                    raise RuntimeError("lost the device")
        (rec,) = P.serve_step_log()
        assert names(rec) == [E.SPAN_FETCH, P.STEP_SPAN]

    def test_rid_may_be_set_inside_the_block(self):
        stats = P.ServeStats()
        with stats.span(P.STEP_SPAN):
            with stats.span(E.SPAN_PLAN) as plan:
                plan.rid = "late"
        (rec,) = P.serve_step_log()
        assert rec["spans"][0][3] == "late"


def test_spans_cost_microseconds():
    """The always-on promise: an empty span costs microseconds, not a
    step's worth (the device number is in PERF.md; this holds the order
    of magnitude wherever the tests run)."""
    import time

    stats = P.ServeStats()
    n = 20_000
    with stats.span(P.STEP_SPAN):
        t0 = time.perf_counter()
        for _ in range(n):
            with stats.span(E.SPAN_FETCH):
                pass
        per_span = (time.perf_counter() - t0) / n
    assert per_span < 50e-6


def test_spans_are_in_a_profiler_capture(params, tmp_path):
    """Under a profiler session the spans are TraceAnnotations on the
    capture's host plane, beside whatever device lines it has."""
    eng = make_engine(params, True, True)
    eng.submit(np.arange(1, 6))
    eng.step()                            # compile outside the capture
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        eng.step()
    (pb,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(pb)
    seen = {e.name for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith(P.STEP_SPAN)}
    assert {E.SPAN_FETCH, E.SPAN_DISPATCH, E.SPAN_SELECT,
            P.STEP_SPAN, *DECODE_SPANS} <= seen
    # Outside a session no annotation is made (one made then would
    # stay inert in a later session, and costs half a span).
    assert eng.stats.span(E.SPAN_FETCH)._ann is None


def test_new_counter_is_mirrored(params):
    """``install_writes`` rides the counters: in ``serve.stats()`` and
    the registry guard's mirrored set."""
    from mpi4torch_tpu.analyze import registry

    eng = make_engine(params, True, False)
    eng.submit(np.arange(1, 6))
    eng.run()
    assert serve.stats()["install_writes"] == 1
    assert serve.stats()["decode_select_syncs"] == 0
    # What the one admission wrote: table, tokens, positions, live
    # mask; the decode-only step behind it sent nothing up.
    assert serve.stats()["steps"] == 2
    assert serve.stats()["decode_uploads"] == 4
    assert serve.stats()["step_compiles"] \
        == sum(r["step_compiles"] for r in log_of(eng))
    assert registry.serve_paging_problems() == []
