"""The cell ``longcat-flash-chat.serve_scmoe_1k``: it rehearses on the
CPU and is correct; with its tokens broken underneath, or with the
float8 control in the program's place, it is not; its cycle is the one
the issue states; the two new metrics name readers that exist and read
made-up records; the family's counts are this configuration's."""

import json
import os
import subprocess
import sys
from collections import Counter

import numpy as np
import pytest

from benchmarks import common, run as harness
from benchmarks.families import longcat_flash as fam
from benchmarks.families import openpangu_moe
from benchmarks.readers import step_count_ratio
from benchmarks.tests.test_harness import bench

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "longcat-flash-chat.serve_scmoe_1k"
NEW_METRICS = ["moe_zero_choice_share.serve", "dense_ffn_time_share.serve"]
CFG = harness.load_json(ROOT, "benchmarks", "configs",
                        "longcat-flash-chat.json")


def rehearse(*more):
    return bench(ROOT, "--workload", CELL, "--seed", "2147483659",
                 "--seconds", "1", "--trace", "0", "--rehearse", *more)


def test_the_cell_rehearses_and_is_correct():
    rc, out, err = rehearse()
    assert rc == 0, err[-2000:]
    result = json.loads(out[-1])
    assert result["correct"] is True and result["attempted"] > 0
    assert result["failed"] == 0
    compared = [l for l in out if l.startswith("compared ")]
    assert len(compared) == 3 and all(l.endswith(" ok") for l in compared)
    notes = [l for l in err.splitlines() if l.startswith("notes ")][-1]
    assert '"rows_a_held_expert_and_decode_step"' in notes
    assert '"compiles_in_window": 0' in notes


def test_wrong_tokens_are_not_correct():
    rc, out, err = rehearse("--break", "wrong_token")
    assert rc == 0, err[-2000:]
    assert json.loads(out[-1])["correct"] is False
    assert any(l.startswith("compared served_logit_gap:")
               and l.endswith("NOT OK") for l in out)


def test_the_float8_control_is_not_correct_on_any_seed():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "control.py"),
         "--workload", CELL, "--seeds", "11,12,13", "--seconds", "1",
         "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert json.loads(lines[-1])["control_not_correct_on_every_seed"] is True
    assert sum(l.startswith("control ") for l in lines) == 3


def test_the_cycle_is_the_issues():
    tr = harness.load_json(ROOT, "benchmarks", "traffic",
                           "serve_scmoe_1k.json")
    cycle = [tuple(c) for c in tr["cycle"]]
    assert len(cycle) == tr["clients"] == tr["engine"]["slots"] == 32
    assert Counter(p for p, _ in cycle) == {512: 8, 1024: 20, 2048: 4}
    for prompt, (a, b, c) in {512: (2, 4, 2), 1024: (5, 10, 5),
                              2048: (1, 2, 1)}.items():
        assert Counter(n for p, n in cycle if p == prompt) \
            == {768: a, 1024: b, 1280: c}
    assert sum(p for p, _ in cycle) == sum(n for _, n in cycle) == 32768
    assert all(cycle[i] != cycle[i - 1] for i in range(32))
    eng = tr["engine"]
    assert (eng["block_size"], eng["num_blocks"], eng["max_new"]) \
        == (128, 1024, 1280)
    assert eng["num_blocks"] * eng["block_size"] \
        == 32 * CFG["max_position_embeddings"]
    assert (tr["stagger_steps"], tr["check_requests"],
            tr["trace_seconds"]) == (5, 3, 8)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_metric_names_a_reader_and_lists_the_cell(metric):
    spec = harness.load_json(ROOT, "BENCHMARK.json")
    entry = harness.by_name(spec["per_layer"], metric, "metric")
    assert CELL in entry["workloads"] and entry["moves"] == "serve_tok_s"
    args = harness.load_json(ROOT, "benchmarks", "metrics", metric + ".json")
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "readers", args["reader"] + ".py"))


def test_the_cell_reports_what_the_other_latent_cell_reports():
    spec = harness.load_json(ROOT, "BENCHMARK.json")
    other = "openpangu-ultra-moe-718b.serve_latent_4k"
    for m in spec["end_to_end"] + spec["per_layer"]:
        if other in m.get("workloads", ()):
            assert CELL in m["workloads"], m["name"]
    assert set(CFG["reduced"]) == set(CFG["published"])
    assert not any("dim" in k or "rank" in k or "size" in k.replace(
        "vocab_size", "") for k in CFG["reduced"])


def test_the_zero_choice_share_reads_the_steps_two_counters():
    args = harness.load_json(ROOT, "benchmarks", "metrics",
                             "moe_zero_choice_share.serve.json")
    ctx = common.Context(root=ROOT, cell={}, cfg=CFG, traffic={}, limits={},
                         peaks={}, seed=0, seconds=1, trace=True,
                         rehearse=False, t_start=0.0)
    step = lambda t, zero, live: {
        "t0_ns": t, "t1_ns": t + 1, "prefill_tokens": 0, "active": 32,
        "moe_zero_pairs": zero, "moe_live_pairs": live}
    rec = common.Record(ctx=ctx, scalars={"setup_s": 0.0, "window_s": 1.0},
                        extras={"step_log": {"cap": 8192, "records": [
                            step(10, 500, 1536), step(20, 524, 1536)]}})
    assert step_count_ratio.read(rec, args) == pytest.approx(100 / 3)
    none = common.Record(ctx=ctx, scalars={"setup_s": 0.0, "window_s": 1.0},
                         extras={"step_log": {"cap": 8192, "records": [
                             step(10, 0, 0)]}})
    assert step_count_ratio.read(none, args) is None


def test_kernel_calls_are_this_configurations():
    """Eight latent reads a decode step at 64 heads; two grouped
    products for each of the four expert layers' rows, at the experts'
    own width."""
    rows = np.zeros((4, 16), int)
    rows[:, 2] = 1
    steps = [{"active": 32, "decode_pages_live": 380,
              "moe_rows": [("decode", rows)]}]
    calls = fam.kernel_calls(CFG, steps, 128)
    latent = calls["paged_latent_attn"]["calls"]
    assert len(latent) == 8 == CFG["num_hidden_layers"]
    flop, nbytes = latent[0]
    assert nbytes == 380 * 128 * 640 * 2
    assert flop == 2 * 380 * 128 * 64 * (640 + 512)
    grouped = calls["moe_grouped_dot.serve"]["calls"]
    assert len(grouped) == 4 * 2
    d, f = CFG["hidden_size"], CFG["expert_ffn_hidden_size"]
    assert grouped[0] == (2 * d * 2 * f, 2 * (d + 2 * f + d * 2 * f))
    assert fam.KERNELS is openpangu_moe.KERNELS
    assert "ffn" in fam.scopes()
