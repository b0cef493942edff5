"""The readers of the program's set-up log (PR 50), each on a
hand-built log: intervals united and not summed, what
``Engine.program_texts()`` compiled left out, the cut at the window's
edge, the count of what the persistent cache answered, the
constructor's spans summed, what is left of ``setup_s``, nothing to
read (never an error) from a program without the log or with a full
ring, and the program's own log through the same readers.  Also: every
metric file added with them names a reader that exists and has its
entry in ``BENCHMARK.json``, by name, and no file the benchmark already
had differs from the commit before."""

import json
import os
import subprocess

import pytest

from benchmarks import common, program_setup, program_spans, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
C = "mpi4torch.serve.construct"
STEP = program_spans.STEP
S = 1_000_000_000
T_START, SETUP_S, WINDOW_S = 100.0, 20.0, 10.0
T0 = int(T_START * 1e9)
T_OPEN = int((T_START + SETUP_S) * 1e9)

SERVING = ["internlm2-1.8b.serve_chat",
           "openpangu-ultra-moe-718b.serve_latent_4k",
           "longcat-flash-chat.serve_scmoe_1k", "glm-5.2.serve_dsa_16k",
           "nemotron-3-super-120b-a12b.serve_ssm_chat",
           "trinity-mini.serve_swa_mix_16k"]
TRAINING = ["mistral-7b-v0.1.train_1chip", "mistral-7b-v0.1.train_dp4",
            "kimi-linear-48b-a3b.train_kda_8k",
            "smallthinker-21ba3b.train_ep4_16k"]
# metric -> the cells it lists (in BENCHMARK.json's order of cells).
NEW_METRICS = {
    "setup_trace_s": "all", "setup_lower_s": "all", "setup_compile_s": "all",
    "setup_cache_read_s": "all", "setup_cache_misses": "all",
    "setup_cache_hits": "all", "setup_first_use_s": SERVING,
    "construct_take_s": SERVING, "construct_shard_s": SERVING,
    "construct_pool_s": SERVING, "setup_unnamed_s": "all",
    "compiles_in_window.train": TRAINING}
NEW_FILES = (["program_setup.py", "tests/test_setup_readers.py"]
             + [f"readers/{r}.py" for r in (
                 "setup_log_seconds", "setup_log_count",
                 "setup_span_seconds", "setup_unnamed_s")]
             + [f"metrics/{m}.json" for m in NEW_METRICS])


def at(seconds: float) -> int:
    """Nanoseconds on the log's clock, ``seconds`` after process start."""
    return T0 + int(seconds * S)


def rec(kind, t0, t1, program="jit(step)", span=None, rid=None, cache=None):
    out = {"kind": kind, "program": program, "t0_ns": at(t0), "t1_ns": at(t1),
           "span": span, "rid": rid, "thread": 1}
    if kind == "compile":
        out.update(cache=cache or "off", retrieval_s=None)
    return out


def span(name, t0, t1, rid=None, engine=0):
    return (name, at(t0), at(t1), rid, engine)


def record(compiles, spans=(), compile_cap=8192, span_cap=4096, **scalars):
    ctx = common.Context(root="", cell={}, cfg={}, traffic={}, limits={},
                         peaks={}, seed=0, seconds=WINDOW_S, trace=True,
                         rehearse=False, t_start=T_START)
    scalars = {"setup_s": SETUP_S, "window_s": WINDOW_S, **scalars}
    log = None if compiles is None else {
        "compiles": compiles, "compile_cap": compile_cap,
        "spans": list(spans), "span_cap": span_cap}
    return common.Record(ctx=ctx, scalars=scalars,
                         extras={"setup_log": log})


def a_setup():
    """A serving set-up of 20 s: the constructor from 2 to 8 s (take 1.5,
    top 0.5, two layers of 1 and 0.75, the pool 1), a prefill program
    first used in a warm-up step (trace 1, lower 0.5, a 2 s miss), the
    decode step read back from the cache in 0.25 s, the programs' texts
    (left out), and a compilation 3 s into the window."""
    spans = [span(C + ".shard.top", 2.0, 2.5),
             span(C + ".shard.take", 2.5, 3.5, 0),
             span(C + ".shard.layer", 3.5, 4.5, 0),
             span(C + ".shard.take", 4.5, 5.0, 1),
             span(C + ".shard.layer", 5.0, 5.75, 1),
             span(C + ".shard.take", 5.75, 5.75, 2),
             span(C + ".shard", 2.0, 5.75),
             span(C + ".pool", 6.0, 7.0),
             span(C + ".install", 7.0, 7.25),
             span(C, 2.0, 8.0),
             span(program_setup.PROGRAM_TEXTS, 15.0, 17.0)]
    compiles = [
        # inside layer 0's sharding: a trace, and while it was lowered
        # another program traced (nested: united, not summed)
        rec("trace", 3.5, 3.75, "shard", C + ".shard.layer", 0),
        rec("trace", 3.8, 3.9, "helper", C + ".shard.layer", 0),
        rec("lower", 3.75, 4.0, "jit(shard)", C + ".shard.layer", 0),
        rec("compile", 4.0, 4.25, "jit(shard)", C + ".shard.layer", 0,
            "miss"),
        # the warm-up's first prefill
        rec("trace", 9.0, 10.0, "prefill", STEP + ".admit.prefill", "r1"),
        rec("lower", 10.0, 10.5, "jit(prefill)", STEP + ".admit.prefill",
            "r1"),
        rec("compile", 10.5, 12.5, "jit(prefill)", STEP + ".admit.prefill",
            "r1", "miss"),
        # the decode step, read back
        rec("trace", 13.0, 13.5, "step", STEP + ".decode.dispatch.call"),
        rec("lower", 13.5, 13.75, "jit(step)",
            STEP + ".decode.dispatch.call"),
        dict(rec("compile", 13.75, 14.0, "jit(step)",
                 STEP + ".decode.dispatch.call", None, "hit"),
             retrieval_s=0.2),
        # no cache met: an eager op of the caller's
        rec("compile", 14.0, 14.125, "jit(add)"),
        # program_texts: left out everywhere
        rec("trace", 15.0, 15.5, "step", program_setup.PROGRAM_TEXTS),
        rec("lower", 15.5, 16.0, "jit(step)", program_setup.PROGRAM_TEXTS),
        rec("compile", 16.0, 17.0, "jit(step)", program_setup.PROGRAM_TEXTS,
            None, "hit"),
        # inside the window, and after it
        rec("compile", 23.0, 23.5, "jit(late)", STEP + ".admit.prefill",
            "r9", "miss"),
        rec("compile", 31.0, 31.5, "jit(reference)")]
    return compiles, spans


def read(name, r):
    return run.read_metric(name, r)


def test_the_cut():
    compiles, spans = a_setup()
    cut = program_setup.cut(record(compiles, spans))
    assert [r["program"] for r in cut["window"]] == ["jit(late)"]
    assert len(cut["setup"]) == 11
    assert all(r["span"] != program_setup.PROGRAM_TEXTS
               for r in cut["setup"] + cut["window"])
    assert len(cut["spans"]) == len(spans)
    assert (cut["t_start_ns"], cut["t_open_ns"]) == (T0, T_OPEN)
    # A record that ends on the window's edge is set-up's; a span that
    # closes after it is not set-up's.
    edge = rec("compile", 19.0, 20.0, "jit(edge)")
    late = span(C + ".pool", 19.5, 20.5)
    cut = program_setup.cut(record(compiles + [edge], spans + [late]))
    assert edge in cut["setup"] and edge not in cut["window"]
    assert late not in cut["spans"]


def test_seconds_are_united_by_kind_and_cache():
    r = record(*a_setup())
    # 0.25 + 0.1 (apart) + 1 + 0.5
    assert read("setup_trace_s", r) == pytest.approx(1.85)
    assert read("setup_lower_s", r) == pytest.approx(0.25 + 0.5 + 0.25)
    # misses and the one that met no cache
    assert read("setup_compile_s", r) == pytest.approx(0.25 + 2.0 + 0.125)
    assert read("setup_cache_read_s", r) == pytest.approx(0.25)
    assert read("setup_cache_misses", r) == 2
    assert read("setup_cache_hits", r) == 1
    # everything under a step's span before the window
    assert read("setup_first_use_s", r) == pytest.approx(3.5 + 1.0)
    assert read("compiles_in_window.train", r) == 1


def test_nested_intervals_are_not_counted_twice():
    outer = rec("trace", 1.0, 3.0, "step")
    inner = rec("trace", 1.5, 2.0, "inner")
    overlapping = rec("trace", 2.5, 3.5, "other")
    r = record([inner, outer, overlapping])
    assert read("setup_trace_s", r) == pytest.approx(2.5)


def test_the_constructors_spans_are_summed():
    r = record(*a_setup())
    assert read("construct_take_s", r) == pytest.approx(1.5)
    assert read("construct_shard_s", r) == pytest.approx(0.5 + 1.0 + 0.75)
    assert read("construct_pool_s", r) == pytest.approx(1.0)
    # a training cell has no engine: nothing to read, not 0
    compiles, _ = a_setup()
    assert read("construct_pool_s", record(compiles)) is None


def test_what_is_left_of_setup():
    compiles, spans = a_setup()
    r = record(compiles, spans)
    # named: take + top + layers (2.0-5.75, the records inside them
    # too), the pool (1), the first prefill (3.5), the decode step (1),
    # the eager op (0.125); the programs' texts are not
    named = 3.75 + 1.0 + 3.5 + 1.0 + 0.125
    assert read("setup_unnamed_s", r) == pytest.approx(SETUP_S - named)
    # a record that began before the process's clock is clipped to it
    early = rec("trace", -1.0, 1.0, "import")
    r = record(compiles + [early], spans)
    assert read("setup_unnamed_s", r) == pytest.approx(SETUP_S - named - 1.0)


def test_nothing_in_the_log_reads_zero_not_nothing():
    r = record([])
    for name in ("setup_trace_s", "setup_lower_s", "setup_compile_s",
                 "setup_cache_read_s", "setup_first_use_s"):
        assert read(name, r) == 0.0
    for name in ("setup_cache_misses", "setup_cache_hits",
                 "compiles_in_window.train"):
        assert read(name, r) == 0
    assert read("setup_unnamed_s", r) == SETUP_S


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_nothing_to_read(name):
    compiles, spans = a_setup()
    # the parent commit: no log at all
    assert read(name, record(None)) is None
    # no window in the record
    r = record(compiles, spans)
    del r.scalars["window_s"]
    assert read(name, r) is None
    # a full ring may have dropped set-up's records
    assert read(name, record(compiles, spans,
                             compile_cap=len(compiles))) is None
    assert read(name, record(compiles, spans, span_cap=len(spans))) is None


def test_the_program_without_the_log(monkeypatch):
    """The parent commit: ``profiling`` has no ``compile_log``."""
    from mpi4torch_tpu.utils import profiling

    monkeypatch.delattr(profiling, "compile_log")
    assert program_setup.setup_log() is None
    r = common.Record(ctx=record([]).ctx,
                      scalars={"setup_s": SETUP_S, "window_s": WINDOW_S})
    assert program_setup.cut(r) is None
    assert r.extras["setup_log"] is None
    for name in NEW_METRICS:
        assert read(name, r) is None


def test_the_program_with_one():
    """The program's own log through the readers: an engine constructed
    and stepped here, set-up ending now."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi4torch_tpu import serve
    from mpi4torch_tpu.models import transformer as T
    from mpi4torch_tpu.utils import profiling

    profiling.reset_serve_stats()
    t_start = time.perf_counter()
    cfg = T.TransformerConfig(vocab=37, d_model=16, n_heads=4, n_layers=2,
                              d_ff=32, max_seq=40)
    params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                dtype=jnp.float32)
    eng = serve.Engine(cfg, params, serve.ServeConfig(slots=2, max_new=3,
                                                      block_size=4),
                       spmd=True, nranks=1)
    eng.submit(np.arange(1, 6))
    eng.run()
    eng.program_texts()
    setup_s = time.perf_counter() - t_start
    ctx = common.Context(root="", cell={}, cfg={}, traffic={}, limits={},
                         peaks={}, seed=0, seconds=1.0, trace=True,
                         rehearse=False, t_start=t_start)
    r = common.Record(ctx=ctx, scalars={"setup_s": setup_s, "window_s": 1.0})
    got = {name: read(name, r) for name in NEW_METRICS}
    log = r.extras["setup_log"]
    assert log["compile_cap"] == profiling.COMPILE_LOG_CAP
    assert any(c["span"] == program_setup.PROGRAM_TEXTS
               for c in log["compiles"])
    assert all(v is not None for v in got.values()), got
    for name in ("setup_trace_s", "setup_lower_s", "setup_compile_s",
                 "setup_first_use_s", "construct_shard_s",
                 "construct_pool_s"):
        assert 0 < got[name] < setup_s, name
    assert got["setup_cache_hits"] + got["setup_cache_misses"] == len(
        [c for c in program_setup.cut(r)["setup"]
         if c.get("cache") in ("hit", "miss")])
    assert got["compiles_in_window.train"] == 0
    assert 0 < got["setup_unnamed_s"] < setup_s
    profiling.reset_serve_stats()


# --- the files -------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_the_metric_file_names_a_reader_and_has_its_entry(name):
    spec = run.load_json(ROOT, "BENCHMARK.json")
    entry = run.by_name(spec["per_layer"], name, "metric")   # by name
    assert entry["moves"] == "setup_s"
    cells = [w["name"] for w in spec["workloads"]]
    want = cells if NEW_METRICS[name] == "all" else NEW_METRICS[name]
    assert entry["workloads"] == want
    assert entry["unit"] == ("count" if entry["source"] == "program_counter"
                             else "s")
    assert entry["source"] in ("program_span", "program_counter")
    layers = {m["layer"] for m in spec["per_layer"]
              if m["name"] not in NEW_METRICS}
    assert entry["layer"] in layers
    args = run.load_json(ROOT, "benchmarks", "metrics", name + ".json")
    assert f"readers/{args['reader']}.py" in NEW_FILES
    assert os.path.exists(os.path.join(ROOT, "benchmarks", "readers",
                                       args["reader"] + ".py"))
    assert set(SERVING) | set(TRAINING) == set(cells)


def test_no_file_the_benchmark_had_was_edited():
    """Against ``HEAD`` where the checkout is a git repository (on the
    machine with the chip it is not: nothing to compare with)."""
    def git(*cmd):
        return subprocess.run(["git", *cmd], cwd=ROOT, capture_output=True,
                              text=True)
    if git("rev-parse", "--verify", "HEAD").returncode != 0:
        pytest.skip("not a git checkout")
    base = "HEAD"
    if git("cat-file", "-e", "HEAD:benchmarks/program_setup.py") \
            .returncode == 0:
        # Committed.  The rule binds the PR that added these files: it
        # is HEAD and is compared with its parent, or it is history.
        added = git("diff", "--name-only", "--diff-filter=A", "HEAD~1",
                    "HEAD", "--", "benchmarks").stdout.split()
        if "benchmarks/program_setup.py" not in added:
            pytest.skip("the PR that added the set-up readers has landed")
        base = "HEAD~1"
    changed = git("diff", "--name-status", base, "--", "benchmarks").stdout
    for line in changed.splitlines():
        status, path = line.split(None, 1)
        assert status == "A" and path[len("benchmarks/"):] in NEW_FILES, line
    old = json.loads(git("show", f"{base}:BENCHMARK.json").stdout)
    new = run.load_json(ROOT, "BENCHMARK.json")
    assert new["per_layer"][:len(old["per_layer"])] == old["per_layer"]
    assert [m["name"] for m in new["per_layer"][len(old["per_layer"]):]] \
        == list(NEW_METRICS)
    for key in old:
        if key != "per_layer":
            assert new[key] == old[key], key
