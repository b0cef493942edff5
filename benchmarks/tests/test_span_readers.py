"""The readers of the program's step log, each on a hand-built log and
trace: the cut to the measured window, the split into admitting and
decoding steps, nothing to read without a log, idle time under a span
on a device line with a known gap, and the alignment's refusals.  Also:
every metric file added with them names a reader that exists and has
its entry in ``BENCHMARK.json``, and no file the benchmark already had
differs from ``HEAD``."""

import json
import os
import subprocess

import pytest

from benchmarks import common, program_spans, run
from benchmarks import trace_reduce as tr
from benchmarks.readers import (idle_under_span, step_count_ratio,
                                step_span_ms, step_unspanned_share)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
S = program_spans.STEP
MS = 1_000_000
T_START, SETUP_S, WINDOW_S = 100.0, 20.0, 10.0
T_OPEN = int((T_START + SETUP_S) * 1e9)

NEW_METRICS = [
    "admit_prefill_ms", "admit_install_ms", "decode_dispatch_ms",
    "decode_fetch_ms", "decode_select_ms", "step_unspanned_share",
    "install_writes_per_ktok", "idle_under_install_share.serve",
    "idle_under_select_share.serve"]
NEW_FILES = (["program_spans.py", "tests/test_span_readers.py"]
             + [f"readers/{r}.py" for r in (
                 "step_span_ms", "step_unspanned_share", "step_count_ratio",
                 "idle_under_span")]
             + [f"metrics/{m}.json" for m in NEW_METRICS])


def decode_step(t0, dispatch=2, fetch=160, select=40, bare=0):
    """A decode-only step record beginning at ``t0`` ns, its phases in
    milliseconds, ``bare`` ms under no child span."""
    spans, t = [], t0
    for name, ms in ((".expire", 0), (".admit", 0),
                     (".decode.dispatch", dispatch), (".decode.fetch", fetch),
                     (".decode.select", select)):
        spans.append((S + name, t, t + ms * MS, None))
        t += ms * MS
    t += bare * MS
    spans.append((S, t0, t, None))
    return {"engine": 0, "t0_ns": t0, "t1_ns": t, "spans": spans,
            "admitted": 0, "prefill_tokens": 0, "install_writes": 0,
            "active": 16}


def admit_step(t0, tokens, prefill=50, install=600, rid="r"):
    """An admitting step: one request of ``tokens`` tokens on pages of
    128 and 48 cache leaves, then a decode."""
    t = t0
    inner = []
    for name, ms in ((".admit.plan", 1), (".admit.prefill", prefill),
                     (".admit.install", install), (".admit.first_token", 3)):
        inner.append((S + name, t, t + ms * MS, rid))
        t += ms * MS
    rec = decode_step(t)
    rec["spans"] = ([(S + ".expire", t0, t0, None)] + inner
                    + [(S + ".admit", t0, t, None)] + rec["spans"][2:-1]
                    + [(S, t0, rec["t1_ns"], None)])
    rec.update(t0_ns=t0, admitted=1, prefill_tokens=tokens,
               install_writes=-(-tokens // 128) * 48)
    return rec


def record(records, cap=8192, trace=None, **scalars):
    ctx = common.Context(root="", cell={}, cfg={}, traffic={}, limits={},
                         peaks={}, seed=0, seconds=WINDOW_S, trace=True,
                         rehearse=False, t_start=T_START)
    scalars = {"setup_s": SETUP_S, "window_s": WINDOW_S, **scalars}
    log = None if records is None else {"records": records, "cap": cap}
    return common.Record(ctx=ctx, scalars=scalars, trace=trace,
                         extras={"step_log": log})


def chain(t0, makers):
    """Step records back to back from ``t0``."""
    out = []
    for make in makers:
        out.append(make(t0))
        t0 = out[-1]["t1_ns"] + 10_000
    return out


def a_run():
    """Three steps before the window, then decode, admit (1,024 tokens),
    decode, admit (256), decode inside it, and one after it."""
    before = chain(T_OPEN - 5_000 * MS, [decode_step] * 3)
    inside = chain(T_OPEN + 5_000, [
        decode_step,
        lambda t: admit_step(t, 1024, prefill=60, install=1200),
        lambda t: decode_step(t, dispatch=4, fetch=170, select=50),
        lambda t: admit_step(t, 256, prefill=20, install=300),
        lambda t: decode_step(t, dispatch=3, fetch=165, select=45)])
    after = chain(T_OPEN + int(WINDOW_S * 1e9) + MS, [decode_step])
    return before, inside, after


def test_window_cut():
    before, inside, after = a_run()
    w = program_spans.window(record(before + inside + after))
    assert w["steps"] == inside and w["before"] == before
    assert w["t_open_ns"] == T_OPEN and not w["dropped"]
    # a step that began inside the window and ended after it belongs
    late = decode_step(T_OPEN + int(WINDOW_S * 1e9) - MS)
    w = program_spans.window(record(before + [late]))
    assert w["steps"] == [late]


def test_admit_and_decode_split():
    before, inside, after = a_run()
    r = record(before + inside + after)
    kinds = {k: program_spans.steps_of(r, k)
             for k in ("all", "admit", "decode")}
    assert kinds["all"] == inside
    assert kinds["admit"] == [inside[1], inside[3]]
    assert kinds["decode"] == [inside[0], inside[2], inside[4]]
    # a step with nothing to decode is neither
    idle = decode_step(T_OPEN + 9_000 * MS)
    idle["active"] = 0
    r = record(inside + [idle])
    assert idle not in program_spans.steps_of(r, "decode")
    assert idle in program_spans.steps_of(r, "all")
    with pytest.raises(ValueError):
        program_spans.steps_of(r, "warm")


def test_step_span_ms():
    before, inside, after = a_run()
    r = record(before + inside + after)
    read = lambda span, steps: step_span_ms.read(
        r, {"span": S + span, "steps": steps})
    assert read(".decode.fetch", "decode") == 165.0      # of 160, 170, 165
    assert read(".decode.select", "decode") == 45.0
    assert read(".decode.dispatch", "decode") == 3.0
    assert read(".admit.install", "admit") == 750.0      # of 1200, 300
    assert read(".admit.prefill", "admit") == 40.0
    assert read("", "admit") == pytest.approx(
        (1264 + 202 + 324 + 202) / 2)
    # a span no step of that kind has reads 0, not nothing
    assert read(".admit.install", "decode") == 0.0
    # two requests admitted in one step: their spans add up
    two = admit_step(T_OPEN + MS, 256, install=300)
    two["spans"].insert(4, (S + ".admit.install", two["t0_ns"],
                            two["t0_ns"] + 100 * MS, "other"))
    assert step_span_ms.read(record([two]), {
        "span": S + ".admit.install", "steps": "admit"}) == 400.0


def test_unspanned_share_and_count_ratio():
    steps = chain(T_OPEN + 5_000, [
        lambda t: decode_step(t, 2, 160, 38, bare=0),
        lambda t: decode_step(t, 2, 160, 38, bare=8)])
    r = record(steps)
    assert step_unspanned_share.read(r, {}) == pytest.approx(
        100.0 * 8 / 408)
    before, inside, after = a_run()
    r = record(before + inside + after)
    assert step_unspanned_share.read(r, {}) == 0.0
    args = {"num": "install_writes", "den": "prefill_tokens", "scale": 1000}
    # 8 + 2 pages x 48 leaves over 1,280 tokens
    assert step_count_ratio.read(r, args) == pytest.approx(375.0)
    # no prefill in the window: nothing to divide by
    assert step_count_ratio.read(record(before + [inside[0]]), args) is None


@pytest.mark.parametrize("reader,args", [
    (step_span_ms, {"span": S + ".decode.fetch", "steps": "decode"}),
    (step_unspanned_share, {}),
    (step_count_ratio, {"num": "install_writes", "den": "prefill_tokens",
                        "scale": 1000}),
    (idle_under_span, {"span": S + ".decode.select"})])
def test_nothing_to_read(reader, args):
    before, inside, _ = a_run()
    trace = tr.Trace(devices={0: tr.DeviceTrace(sync=[("fusion", 0, MS)])},
                     host=[("bench.engine_step.decode", 0, MS)])
    assert reader.read(record(None, trace=trace), args) is None     # no log
    assert reader.read(record([], trace=trace), args) is None       # empty
    if reader is not idle_under_span:       # no step in the window
        assert reader.read(record(before, trace=trace), args) is None
    # no window in the record
    r = record(before + inside, trace=trace)
    del r.scalars["window_s"]
    assert reader.read(r, args) is None


def test_a_full_ring_that_begins_inside_the_window_is_not_trusted():
    _, inside, _ = a_run()
    args = {"span": S + ".decode.fetch", "steps": "decode"}
    assert step_span_ms.read(record(inside, cap=len(inside)), args) is None
    assert step_unspanned_share.read(record(inside, cap=len(inside)),
                                     {}) is None
    # full, but its oldest step is from before the window: whole
    before, inside, _ = a_run()
    log = before[-1:] + inside
    assert step_span_ms.read(record(log, cap=len(log)), args) == 165.0


def test_the_program_without_a_step_log(monkeypatch):
    """The parent commit: ``profiling`` has no ``serve_step_log``."""
    from mpi4torch_tpu.utils import profiling

    monkeypatch.delattr(profiling, "serve_step_log")
    assert program_spans.step_log() is None
    r = common.Record(ctx=record([]).ctx,
                      scalars={"setup_s": SETUP_S, "window_s": WINDOW_S})
    assert program_spans.window(r) is None
    assert r.extras["step_log"] is None
    for name in NEW_METRICS:
        assert run.read_metric(name, r) is None


def test_the_program_with_one():
    from mpi4torch_tpu.utils import profiling

    profiling.reset_serve_stats()
    stats = profiling.ServeStats()
    with stats.span(S):
        with stats.span(S + ".decode.fetch"):
            pass
    log = program_spans.step_log()
    assert log["cap"] == profiling.STEP_LOG_CAP
    (rec,) = log["records"]
    assert [s[0] for s in rec["spans"]] == [S + ".decode.fetch", S]
    assert set(rec) >= {"t0_ns", "t1_ns", "prefill_tokens", "active",
                        "install_writes", "admitted"}
    profiling.reset_serve_stats()


# --- idle under a span ---------------------------------------------------

OFFSET = 7_000_000_000_123          # trace clock - program clock, ns


def traced(skew_ns=(0, 0, 0), drop_wrap=False):
    """Three traced steps before the window (admit 1,024 tokens, decode,
    decode) and the trace that holds them: the chip is busy except for
    400 ms inside the install span, 30 ms inside each select span and
    the 20 us after the last step."""
    steps = chain(T_OPEN - 4_000 * MS, [
        lambda t: admit_step(t, 1024, prefill=60, install=1200),
        decode_step, decode_step])
    wraps, sync = [], []
    for r, skew in zip(steps, skew_ns):
        a, b = r["t0_ns"] + OFFSET, r["t1_ns"] + OFFSET
        wraps.append(("bench.engine_step.admit" if r["admitted"]
                      else "bench.engine_step.decode",
                      a - 20_000 + skew, b + 20_000 + skew))
        gaps = []
        for n, t0, t1, _ in r["spans"]:
            if n == S + ".admit.install":
                gaps.append((t0 + OFFSET + 100 * MS, t0 + OFFSET + 500 * MS))
            if n == S + ".decode.select":
                gaps.append((t0 + OFFSET + 5 * MS, t0 + OFFSET + 35 * MS))
        t = a - 20_000
        for g0, g1 in sorted(gaps):
            sync.append(("fusion", t, g0))
            t = g1
        sync.append(("fusion", t, b))
    if drop_wrap:
        wraps = wraps[1:]
    trace = tr.Trace(devices={0: tr.DeviceTrace(sync=sync)}, host=wraps)
    return steps, trace


def test_idle_under_span_on_a_known_gap():
    steps, trace = traced()
    r = record(chain(T_OPEN - 9_000 * MS, [decode_step] * 4) + steps
               + chain(T_OPEN + 5_000, [decode_step]), trace=trace)
    got_steps, offset = idle_under_span.align(r)
    assert got_steps == steps
    assert abs(offset - OFFSET) <= 20_000
    # idle: 400 ms under install, 3 x 30 under select, 0.02 at the end
    idle = 400 + 90 + 0.02
    share = lambda span: idle_under_span.read(r, {"span": S + span})
    assert share(".admit.install") == pytest.approx(100 * 400 / idle,
                                                    rel=1e-3)
    assert share(".decode.select") == pytest.approx(100 * 90 / idle,
                                                    rel=1e-3)
    assert share(".admit.prefill") == 0.0
    assert share("") == pytest.approx(100 * 490 / idle, rel=1e-3)


def test_idle_under_span_refuses():
    args = {"span": S + ".admit.install"}
    # one wrap fewer than there are steps before the window is fine (the
    # phase is the LAST steps), but more wraps than steps is not
    steps, trace = traced()
    assert idle_under_span.read(record(steps[1:], trace=trace), args) is None
    # one offset 2 ms off
    steps, trace = traced(skew_ns=(0, 2 * MS, 0))
    assert idle_under_span.read(record(steps, trace=trace), args) is None
    # half a millisecond off is inside the tolerance
    steps, trace = traced(skew_ns=(0, MS // 2, 0))
    assert idle_under_span.read(record(steps, trace=trace), args) is not None
    # the counts differ because an untraced step slipped in before the
    # window: the last N records are then the wrong ones, and their
    # offsets say so
    steps, trace = traced()
    late = chain(steps[-1]["t1_ns"] + 50 * MS, [decode_step])
    assert idle_under_span.read(record(steps + late, trace=trace),
                                args) is None
    # no trace, no device
    assert idle_under_span.read(record(steps), args) is None
    assert idle_under_span.read(
        record(steps, trace=tr.Trace(host=trace.host)), args) is None


# --- the files -------------------------------------------------------------


def test_new_metric_files_name_readers_and_have_entries():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in spec["per_layer"]]
    assert names[-len(NEW_METRICS):] == NEW_METRICS
    e2e = {m["name"] for m in spec["end_to_end"]}
    layers = {m["layer"] for m in spec["per_layer"][:-len(NEW_METRICS)]}
    for m in spec["per_layer"][-len(NEW_METRICS):]:
        assert m["workloads"] == ["internlm2-1.8b.serve_chat"]
        assert m["layer"] in layers and m["moves"] in e2e
        args = json.load(open(os.path.join(
            ROOT, "benchmarks", "metrics", m["name"] + ".json")))
        path = os.path.join(ROOT, "benchmarks", "readers",
                            args["reader"] + ".py")
        assert os.path.exists(path), path


def test_no_file_the_benchmark_had_was_edited():
    """Against ``HEAD`` where the checkout is a git repository (on the
    machine with the chip it is not: nothing to compare with)."""
    def git(*cmd):
        return subprocess.run(["git", *cmd], cwd=ROOT, capture_output=True,
                              text=True)
    if git("rev-parse", "--verify", "HEAD").returncode != 0:
        pytest.skip("not a git checkout")
    base = "HEAD"
    if git("cat-file", "-e", "HEAD:benchmarks/program_spans.py") \
            .returncode == 0:
        # Committed.  The rule binds the PR that added these files: it
        # is HEAD and is compared with its parent, or it is history.
        added = git("diff", "--name-only", "--diff-filter=A", "HEAD~1",
                    "HEAD", "--", "benchmarks").stdout.split()
        if "benchmarks/program_spans.py" not in added:
            pytest.skip("the PR that added the span readers has landed")
        base = "HEAD~1"
    changed = git("diff", "--name-status", base, "--", "benchmarks").stdout
    for line in changed.splitlines():
        status, path = line.split(None, 1)
        assert status == "A" and path[len("benchmarks/"):] in NEW_FILES, line
    old = json.loads(git("show", f"{base}:BENCHMARK.json").stdout)
    new = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert new["per_layer"][:len(old["per_layer"])] == old["per_layer"]
    for key in old:
        if key != "per_layer":
            assert new[key] == old[key], key
