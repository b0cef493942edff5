"""``readers/idle_owners.py`` and the other readers added with it
(ISSUE 36), on a made-up step log and trace with a known skew between
the host line's clock and the device's, and on a small serving trace
recorded on a v5e with its step log (``record_serve_trace.py``): the
skew comes back inside its bracket, a known idle pattern is shared out
exactly and adds up to 100, and there is nothing to read (and nothing
raises) on an empty or wide bracket, without a step log, and on the
step log of a program that has neither the child spans nor the new
counts.  Also: every metric added with them has its entry in
``BENCHMARK.json`` for the three serving cells and a file that names a
reader that exists."""

import copy
import json
import os

import pytest

from benchmarks import common, program_spans, run
from benchmarks import trace_reduce as tr
from benchmarks.readers import (idle_owners, step_count_sum, step_gap_ms,
                                step_span_ms_where_spanned)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
S = program_spans.STEP
US, MS = 1_000, 1_000_000
T_START, SETUP_S, WINDOW_S = 100.0, 20.0, 10.0
T_OPEN = int((T_START + SETUP_S) * 1e9)
OFFSET = 7_000_000_000_123          # host line's clock - program's, ns
SKEW = 640 * US                     # device's clock - host line's
EDGE = 5 * MS                       # traced before and after the steps

SERVING = ["internlm2-1.8b.serve_chat",
           "openpangu-ultra-moe-718b.serve_latent_4k",
           "longcat-flash-chat.serve_scmoe_1k"]
SPAN_MS = ["decode_dispatch_inputs_ms", "decode_dispatch_call_ms",
           "decode_fetch_tokens_ms", "decode_fetch_counters_ms"]
SHARES = ["idle_under_dispatch_inputs_share.serve",
          "idle_under_dispatch_call_share.serve",
          "idle_under_fetch_share.serve", "idle_under_admit_share.serve",
          "idle_between_steps_share.serve", "idle_unowned_share.serve"]
FROM_TRACE = SHARES + ["trace_clock_skew_us.serve",
                       "trace_clock_bracket_us.serve",
                       "decode_result_latency_ms"]
NEW_METRICS = SPAN_MS + ["decode_uploads_per_token", "step_gap_ms",
                         "step_compiles_in_window"] + FROM_TRACE
NEW_COUNTS = ("decode_uploads", "step_compiles")

# A decode step's phases, ms: inputs, call, tokens, counters, select;
# then the caller's time before the next step.
INPUTS, CALL, TOKENS, COUNTERS, SELECT, GAP = 0.6, 1.0, 9.0, 0.3, 0.1, 0.15


def ns(ms: float) -> int:
    return int(round(ms * MS))


def decode_step(t0: int, launch: float, latency: float, busy: list,
                uploads: int = 4) -> dict:
    """A decode-only step record from ``t0`` (program clock); the
    device runs from ``launch`` ms after ``dispatch.call`` began to
    ``latency`` ms before ``fetch.tokens`` returned (appended to
    ``busy``, host line's clock)."""
    t = t0
    spans = [(S + ".expire", t, t, None), (S + ".admit", t, t, None)]

    def phase(name, children):
        nonlocal t
        start = t
        for child, ms in children:
            spans.append((f"{S}.decode.{name}.{child}", t, t + ns(ms), None))
            t += ns(ms)
        spans.append((f"{S}.decode.{name}", start, t, None))
        return start

    d0 = phase("dispatch", (("inputs", INPUTS), ("call", CALL)))
    phase("fetch", (("tokens", TOKENS), ("counters", COUNTERS)))
    tokens_end = t - ns(COUNTERS)
    spans.append((S + ".decode.select", t, t + ns(SELECT), None))
    t += ns(SELECT)
    spans.append((S, t0, t, None))
    busy.append((d0 + ns(INPUTS) + ns(launch) + OFFSET,
                 tokens_end - ns(latency) + OFFSET))
    return {"engine": 0, "t0_ns": t0, "t1_ns": t, "spans": spans,
            "admitted": 0, "prefill_tokens": 0, "install_writes": 0,
            "active": 16, "decode_uploads": uploads, "step_compiles": 0}


# What the admitting step's phases take, and how long the chip waits
# inside each, ms.
PLAN, PREFILL, INSTALL, FIRST = 0.05, 30.0, 0.5, 3.0
PREFILL_IDLE, FIRST_IDLE = 0.5 + 0.1, 0.2 + 0.1


def admit_step(t0: int, launch: float, latency: float, busy: list) -> dict:
    """An admitting step: plan, prefill (the chip busy but for 0.5 ms
    at its start and 0.1 at its end), install (idle), first token (busy
    but for 0.2 and 0.1), then a decode."""
    t, inner = t0, []
    for name, ms, lead, tail in (("plan", PLAN, None, None),
                                 ("prefill", PREFILL, 0.5, 0.1),
                                 ("install", INSTALL, None, None),
                                 ("first_token", FIRST, 0.2, 0.1)):
        inner.append((f"{S}.admit.{name}", t, t + ns(ms), "r"))
        if lead is not None:
            busy.append((t + ns(lead) + OFFSET, t + ns(ms - tail) + OFFSET))
        t += ns(ms)
    rec = decode_step(t, launch, latency, busy)
    rec["spans"] = ([(S + ".expire", t0, t0, None)] + inner
                    + [(S + ".admit", t0, t, None)] + rec["spans"][2:-1]
                    + [(S, t0, rec["t1_ns"], None)])
    rec.update(t0_ns=t0, admitted=1, prefill_tokens=1024, install_writes=1)
    return rec


def traced(launches, latencies, admit_at=None, skew=SKEW):
    """Step records back to back (``GAP`` ms apart) that end before the
    window opens, and the trace that holds them: one burst of device
    events a program, on a clock ``skew`` ahead of the host line's, and
    the harness's wrappers; the trace runs ``EDGE`` beyond the steps at
    both ends."""
    steps, busy = [], []
    t = T_OPEN - 2_000 * MS
    for i, (launch, latency) in enumerate(zip(launches, latencies)):
        make = admit_step if i == admit_at else decode_step
        steps.append(make(t, launch, latency, busy))
        t = steps[-1]["t1_ns"] + ns(GAP)
    wraps = [("bench.engine_step.admit" if r["admitted"]
              else "bench.engine_step.decode",
              r["t0_ns"] + OFFSET, r["t1_ns"] + OFFSET) for r in steps]
    host = ([("bench.submit", wraps[0][1] - EDGE, wraps[0][1] - EDGE + US)]
            + wraps
            + [("bench.submit", wraps[-1][2] + EDGE - US,
                wraps[-1][2] + EDGE)])
    sync = []
    for a, b in busy:           # a program is several events, gaps of 2 us
        third = (b - a) // 3
        sync += [("fusion.1", a + skew, a + third + skew),
                 ("fusion.2", a + third + 2 * US + skew, b + skew)]
    return steps, tr.Trace(devices={0: tr.DeviceTrace(sync=sync)}, host=host)


def record(records, trace=None, cap=8192):
    ctx = common.Context(root="", cell={}, cfg={}, traffic={}, limits={},
                         peaks={}, seed=0, seconds=WINDOW_S, trace=True,
                         rehearse=False, t_start=T_START)
    log = None if records is None else {"records": records, "cap": cap}
    return common.Record(ctx=ctx, trace=trace, extras={"step_log": log},
                         scalars={"setup_s": SETUP_S, "window_s": WINDOW_S})


def metric(name: str, rec):
    return run.read_metric(name, rec)


def as_the_parent_logs(records: list) -> list:
    """The same steps from a program that has neither the child spans
    nor the new counts."""
    out = copy.deepcopy(records)
    for r in out:
        r["spans"] = [s for s in r["spans"]
                      if s[0].count(".") < S.count(".") + 3]
        for k in NEW_COUNTS:
            del r[k]
    return out


# Launch and result latencies of nine decode steps, ms: the smallest of
# each is 0.1 (in steps that are paired whether or not step 3 admits),
# so the bracket is SKEW -+ 0.1 ms and its middle SKEW.
LAUNCH = [0.5, 0.4, 0.1, 0.6, 0.5, 0.3, 0.5, 0.45, 0.5]
LATENCY = [0.2, 0.3, 0.25, 0.2, 0.2, 0.15, 0.1, 0.2, 0.3]


# --- the skew --------------------------------------------------------------


def test_the_skew_comes_back_inside_its_bracket():
    steps, trace = traced(LAUNCH, LATENCY)
    r = record(steps, trace)
    assert metric("trace_clock_skew_us.serve", r) == SKEW / US
    assert metric("trace_clock_bracket_us.serve", r) == 100.0
    acc = idle_owners.account(r)
    assert acc["paired"] == len(steps) - 1
    # fetch.tokens end - the burst's end: the median of LATENCY[1:]
    assert metric("decode_result_latency_ms", r) == pytest.approx(0.2)
    # an unsymmetric bracket: the middle is off by less than its half
    # width
    steps, trace = traced([0.5] * 9, LATENCY)
    r = record(steps, trace)
    got = metric("trace_clock_skew_us.serve", r)
    half = metric("trace_clock_bracket_us.serve", r)
    assert half == pytest.approx(300.0)          # (0.5 + 0.1) / 2 ms
    assert got == pytest.approx(SKEW / US + 200.0)
    assert abs(got - SKEW / US) <= half


@pytest.mark.parametrize("skew_us", [-300, 0, 450, 980])
def test_any_skew(skew_us):
    steps, trace = traced(LAUNCH, LATENCY, skew=skew_us * US)
    assert metric("trace_clock_skew_us.serve",
                  record(steps, trace)) == skew_us


def test_an_admitting_step_is_not_paired():
    steps, trace = traced(LAUNCH, LATENCY, admit_at=3)
    acc = idle_owners.account(record(steps, trace))
    # neither the admitting step nor the decode step after it
    assert acc["paired"] == len(steps) - 3
    assert acc["skew_ns"] == SKEW


# --- the idle time ---------------------------------------------------------


def test_a_known_idle_pattern_is_shared_out_exactly():
    steps, trace = traced(LAUNCH, LATENCY, admit_at=3)
    r = record(steps, trace)
    n = len(steps)
    want = {
        "idle_under_dispatch_inputs_share.serve": n * INPUTS,
        "idle_under_dispatch_call_share.serve": sum(LAUNCH),
        "idle_under_fetch_share.serve": sum(LATENCY) + n * COUNTERS,
        # plan, the prefill's two ends and the first token's: not install
        "idle_under_admit_share.serve": PLAN + PREFILL_IDLE + FIRST_IDLE,
        "idle_between_steps_share.serve": (n - 1) * GAP,
        # the trace's two ends, and the 2 us between a program's events
        "idle_unowned_share.serve": 2 * EDGE / MS}
    inside = 0.002 * (n + 2)           # split events: n decodes, 2 admits
    total = sum(want.values()) + n * SELECT + INSTALL + inside
    assert idle_owners.account(r)["total_idle_ns"] == pytest.approx(
        total * MS, abs=10)
    got = {name: metric(name, r) for name in SHARES}
    # the 2 us gaps lie under fetch.tokens, the prefill and first_token
    want["idle_under_fetch_share.serve"] += 0.002 * n
    want["idle_under_admit_share.serve"] += 0.002 * 2
    for name in SHARES:
        assert got[name] == pytest.approx(100 * want[name] / total,
                                          abs=1e-4), name
    # with the two old owners, on the one clock, the split is whole
    select = idle_owners.read(r, {"read": "share",
                                  "under": [S + ".decode.select"]})
    install = idle_owners.read(r, {"read": "share",
                                   "under": [S + ".admit.install"]})
    assert select == pytest.approx(100 * n * SELECT / total, abs=1e-4)
    assert install == pytest.approx(100 * INSTALL / total, abs=1e-4)
    assert sum(got.values()) + select + install == pytest.approx(100.0)


def test_the_wait_before_a_paired_step_by_owner():
    """The printed account: the chip's wait before a paired decode
    step's burst, in ms, whole and by owner."""
    steps, trace = traced(LAUNCH, LATENCY)
    rep = idle_owners.report(idle_owners.account(record(steps, trace)))
    n = len(steps) - 1
    by = rep["wait_ms_by"]
    assert by[S + ".decode.dispatch.inputs"] == pytest.approx(INPUTS)
    assert by[S + ".decode.dispatch.call"] == pytest.approx(
        sum(LAUNCH[1:]) / n, abs=1e-4)
    assert by[S + ".decode.fetch.tokens"] == pytest.approx(
        sum(LATENCY[:-1]) / n, abs=1e-4)
    assert by[S + ".decode.fetch.counters"] == pytest.approx(COUNTERS)
    assert by[S + ".decode.select"] == pytest.approx(SELECT)
    assert by[idle_owners.BETWEEN] == pytest.approx(GAP)
    assert rep["wait_ms_a_paired_step"] == pytest.approx(sum(by.values()),
                                                         abs=1e-3)
    assert rep["launch_latency_ms"] == pytest.approx(0.475)


def test_the_innermost_span_owns():
    rec = decode_step(0, 0.5, 0.2, [])
    own = dict(idle_owners.own_intervals(rec))
    assert tr.length(own[S + ".decode.dispatch"]) == 0
    assert tr.length(own[S + ".decode.fetch"]) == 0
    assert tr.length(own[S + ".decode.fetch.tokens"]) == ns(TOKENS)
    assert tr.length(own[S]) == 0
    # time under no child is the parent's own
    rec["spans"] = [s for s in rec["spans"]
                    if not s[0].endswith(".counters")]
    own = dict(idle_owners.own_intervals(rec))
    assert tr.length(own[S + ".decode.fetch"]) == ns(COUNTERS)


# --- nothing to read -------------------------------------------------------


def test_an_empty_or_wide_bracket_reads_nothing():
    # a burst that begins 0.5 ms before its call: no skew satisfies both
    steps, trace = traced([0.5, 0.4, -0.5, 0.6], [0.2, 0.3, 0.1, 0.1])
    r = record(steps, trace)
    assert idle_owners.account(r) is None
    for name in FROM_TRACE:
        assert metric(name, r) is None
    # every launch 1.95 ms after the call began: 2.05 ms wide
    steps, trace = traced([1.95] * 5, [0.1] * 5)
    r = record(steps, trace)
    for name in FROM_TRACE:
        assert metric(name, r) is None
    # 0.3 ms and 1.1 ms back, as on the chip: 1.4 wide, read
    steps, trace = traced([0.3] * 5, [1.1] * 5)
    assert metric("trace_clock_bracket_us.serve",
                  record(steps, trace)) == pytest.approx(700.0)


def test_nothing_paired_reads_nothing():
    # admissions only; one decode step alone
    for launches, admit_at in (([0.5], 0), ([0.5], None)):
        steps, trace = traced(launches, [0.1], admit_at=admit_at)
        r = record(steps, trace)
        for name in FROM_TRACE:
            assert metric(name, r) is None


def test_no_trace_no_device_no_log():
    steps, trace = traced(LAUNCH, LATENCY)
    for r in (record(steps), record(None, trace), record([], trace),
              record(steps, tr.Trace(host=trace.host)),
              # a wrapper too few: the steps cannot be laid on the line
              record(steps[:3], trace)):
        for name in FROM_TRACE:
            assert metric(name, r) is None


def test_the_parents_step_log_reads_nothing():
    """A step log whose records have neither the child spans nor the
    new counts, in the traced phase and in the window: every new metric
    reads nothing, and the old ones read as they did."""
    steps, trace = traced(LAUNCH, LATENCY, admit_at=3)
    busy = []
    t, inside = T_OPEN + 5 * US, []
    for _ in range(4):
        inside.append(decode_step(t, 0.5, 0.2, busy))
        t = inside[-1]["t1_ns"] + ns(GAP)
    new = record(steps + inside, trace)
    old = record(as_the_parent_logs(steps + inside), trace)
    for name in NEW_METRICS:
        assert metric(name, old) is None, name
        assert metric(name, new) is not None, name
    for name in ("decode_dispatch_ms", "decode_fetch_ms",
                 "idle_under_select_share.serve", "step_unspanned_share"):
        assert metric(name, old) == metric(name, new) is not None


def test_the_program_without_a_step_log(monkeypatch):
    from mpi4torch_tpu.utils import profiling

    monkeypatch.delattr(profiling, "serve_step_log")
    r = common.Record(ctx=record([]).ctx,
                      scalars={"setup_s": SETUP_S, "window_s": WINDOW_S})
    for name in NEW_METRICS:
        assert metric(name, r) is None


# --- the window's readers --------------------------------------------------


def in_window(n=5, **kw):
    busy, out, t = [], [], T_OPEN + 5 * US
    for _ in range(n):
        out.append(decode_step(t, 0.5, 0.2, busy, **kw))
        t = out[-1]["t1_ns"] + ns(GAP)
    return out


def test_the_child_spans_medians():
    r = record(in_window())
    want = dict(zip(SPAN_MS, (INPUTS, CALL, TOKENS, COUNTERS)))
    for name in SPAN_MS:
        assert metric(name, r) == pytest.approx(want[name])
    assert metric("decode_dispatch_ms", r) == pytest.approx(INPUTS + CALL)
    assert metric("decode_fetch_ms", r) == pytest.approx(TOKENS + COUNTERS)
    # a span of a kind of step that has it nowhere: nothing, where
    # step_span_ms reads 0
    args = {"span": S + ".decode.fetch.tokens", "steps": "admit"}
    assert step_span_ms_where_spanned.read(r, args) is None


def test_uploads_per_token_gap_and_compiles():
    steps = in_window(uploads=4)
    r = record(steps)
    assert metric("decode_uploads_per_token", r) == 0.25      # 4 / 16
    assert metric("step_gap_ms", r) == pytest.approx(GAP)
    assert metric("step_compiles_in_window", r) == 0
    steps[2]["step_compiles"] = 2
    steps[2]["compiles"] = [(S + ".admit.prefill", "r", 1.5)] * 2
    assert step_count_sum.read(record(steps),
                               {"count": "step_compiles"}) == 2
    # the gap is between steps of one engine
    other = copy.deepcopy(steps[1])
    other["engine"] = 1
    r = record(sorted(steps + [other], key=lambda s: s["t0_ns"]))
    assert step_gap_ms.read(r, {}) \
        == pytest.approx(GAP)
    # one step in the window: no gap to read
    assert step_gap_ms.read(record(steps[:1]), {}) is None
    # a full ring that begins inside the window is not trusted
    r = record(steps, cap=len(steps))
    for name in ("step_gap_ms", "step_compiles_in_window",
                 "decode_uploads_per_token", *SPAN_MS):
        assert metric(name, r) is None


# --- a recorded run ----------------------------------------------------------


def recorded():
    """The run ``record_serve_trace.py`` recorded on a v5e: what it
    wrote, and the record it came from."""
    with open(os.path.join(DATA, "v5e_serve_1chip_small.json")) as f:
        d = json.load(f)
    trace = tr.Trace(
        devices={0: tr.DeviceTrace(sync=[tuple(e) for e in d["sync"]])},
        host=[tuple(h) for h in d["host"]])
    ctx = common.Context(root="", cell={}, cfg={}, traffic={}, limits={},
                         peaks={}, seed=0, seconds=1.0, trace=True,
                         rehearse=False, t_start=d["t_start"])
    return d, common.Record(ctx=ctx, trace=trace, scalars=d["scalars"],
                            extras={"step_log": d["step_log"]})


def test_the_recorded_run_reads_as_it_did_on_the_chip():
    d, rec = recorded()
    assert d["device_kind"] == "TPU v5 lite"
    for name in NEW_METRICS + ["idle_under_select_share.serve",
                               "idle_under_install_share.serve",
                               "device_idle_share.serve"]:
        assert metric(name, rec) == pytest.approx(d["metrics"][name]), name
    acc = idle_owners.account(rec)
    assert acc["paired"] >= 20
    # children cover their parents, medians do not add exactly
    for parent, kids in (("decode_dispatch_ms", SPAN_MS[:2]),
                         ("decode_fetch_ms", SPAN_MS[2:])):
        assert sum(metric(k, rec) for k in kids) == pytest.approx(
            metric(parent, rec), abs=0.05)
    # the split is whole, on the one clock
    one_clock = [idle_owners.read(rec, {"read": "share", "under": [
        S + span]}) for span in (".decode.select", ".admit.install")]
    assert sum(metric(n, rec) for n in SHARES) + sum(one_clock) \
        == pytest.approx(100.0)
    assert metric("decode_uploads_per_token", rec) == 0.25
    assert metric("step_compiles_in_window", rec) == 0


def test_the_recorded_run_as_the_parent_would_have_logged_it():
    d, rec = recorded()
    rec.extras["step_log"]["records"] = as_the_parent_logs(
        rec.extras["step_log"]["records"])
    for name in NEW_METRICS:
        assert metric(name, rec) is None, name
    for name in ("decode_dispatch_ms", "idle_under_select_share.serve"):
        assert metric(name, rec) == pytest.approx(d["metrics"][name])


# --- the engine's own log ----------------------------------------------------


def test_the_programs_records_have_what_the_readers_read():
    from mpi4torch_tpu.serve import engine
    from mpi4torch_tpu.utils import profiling

    if not hasattr(engine, "SPAN_DISPATCH_INPUTS"):
        pytest.skip("a program from before the child spans")
    profiling.reset_serve_stats()
    stats = profiling.ServeStats()
    with stats.span(S):
        with stats.span(engine.SPAN_DISPATCH):
            with stats.span(engine.SPAN_DISPATCH_INPUTS):
                stats.count("decode_uploads", 4)
            with stats.span(engine.SPAN_DISPATCH_CALL):
                pass
        with stats.span(engine.SPAN_FETCH):
            with stats.span(engine.SPAN_FETCH_TOKENS):
                pass
            with stats.span(engine.SPAN_FETCH_COUNTERS):
                pass
    (rec,) = program_spans.step_log()["records"]
    assert engine.SPAN_DISPATCH_CALL == idle_owners.CALL
    assert engine.SPAN_FETCH_TOKENS == idle_owners.TOKENS
    assert idle_owners.span_of(rec, idle_owners.CALL) is not None
    assert idle_owners.span_of(rec, idle_owners.TOKENS) is not None
    assert rec["decode_uploads"] == 4 and rec["step_compiles"] == 0
    for name in SPAN_MS:
        span = json.load(open(os.path.join(
            ROOT, "benchmarks", "metrics", name + ".json")))["span"]
        assert span in [s[0] for s in rec["spans"]]
    profiling.reset_serve_stats()


# --- the files ---------------------------------------------------------------


def test_new_metrics_have_entries_and_files():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in spec["per_layer"]}
    e2e = {m["name"] for m in spec["end_to_end"]}
    layer = entries["decode_dispatch_ms"]["layer"]
    for name in NEW_METRICS:
        m = entries[name]
        assert set(SERVING) <= set(m["workloads"])
        assert m["layer"] == layer and m["moves"] in e2e
        assert m["source"] in ("program_span", "program_counter",
                               "device_trace")
        args = json.load(open(os.path.join(
            ROOT, "benchmarks", "metrics", name + ".json")))
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "readers", args["reader"] + ".py"))
    assert entries["idle_under_admit_share.serve"]["moves"] == "ttft_p50_ms"
    assert entries["step_compiles_in_window"]["moves"] == "ttft_p50_ms"
    # the owners the six shares name, with select and install, leave
    # nothing out and count nothing twice
    owners = [S, S + ".expire", S + ".admit", S + ".admit.plan",
              S + ".admit.prefill", S + ".admit.install",
              S + ".admit.first_token", S + ".decode.dispatch",
              S + ".decode.dispatch.inputs", S + ".decode.dispatch.call",
              S + ".decode.fetch", S + ".decode.fetch.tokens",
              S + ".decode.fetch.counters", S + ".decode.select",
              idle_owners.BETWEEN, idle_owners.OUTSIDE]
    files = [json.load(open(os.path.join(
        ROOT, "benchmarks", "metrics", n + ".json"))) for n in SHARES]
    files += [{"under": [S + ".decode.select"]},
              {"under": [S + ".admit.install"]}]
    for owner in owners:
        takers = [f for f in files
                  if idle_owners.below(owner, f["under"])
                  and not idle_owners.below(owner, f.get("except", []))]
        assert len(takers) == 1, owner
