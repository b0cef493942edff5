"""The ``serve_family`` kind: the new cell rehearses on the CPU and is
correct; with its tokens broken underneath, or with the float8 control
in the program's place, it is not; every new metric file names a reader
that exists; the events of a trace are named after their programs; the
family's counts and the new reader on made-up tables."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import common, run as harness
from benchmarks.families import openpangu_moe as fam
from benchmarks.readers import experts_touched, kernel_roofline, \
    scope_time_share
from benchmarks.tests.test_harness import bench
from benchmarks.trace_reduce import DeviceTrace, Trace
from benchmarks.traffic_kinds import serve_family

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "openpangu-ultra-moe-718b.serve_latent_4k"
NEW_METRICS = ["mla_time_share.serve", "moe_time_share.serve",
               "paged_latent_attn_roofline",
               "moe_grouped_dot_roofline.serve",
               "moe_experts_touched.serve"]


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


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_metric_names_a_reader_and_lists_the_cell(metric):
    spec = harness.load_json(ROOT, "BENCHMARK.json")
    entry = harness.by_name(spec["per_layer"], metric, "metric")
    assert entry["workloads"] == [CELL] and entry["moves"] == "serve_tok_s"
    args = harness.load_json(ROOT, "benchmarks", "metrics", metric + ".json")
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "readers", args["reader"] + ".py"))


def test_the_cell_reports_what_the_other_serving_cell_reports():
    spec = harness.load_json(ROOT, "BENCHMARK.json")
    chat = "internlm2-1.8b.serve_chat"
    for m in spec["end_to_end"] + spec["per_layer"]:
        if chat in m.get("workloads", ()):
            assert (CELL in m["workloads"]) \
                == (m["name"] != "flash_fwd_roofline.serve"), m["name"]
    cfg = harness.load_json(ROOT, "benchmarks", "configs",
                            "openpangu-ultra-moe-718b.json")
    assert set(cfg["reduced"]) == set(cfg["published"])
    assert not any("dim" in k or "rank" in k or "size" in k.replace(
        "vocab_size", "") for k in cfg["reduced"])


# ------------------------------------------ events named by their program

DECODE = """
  %fusion.3 = bf16[32,7680]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(sm)/mpi4torch.serve.decode_step/mpi4torch.moe/mul"}
  %mpi4torch_paged_latent_attn.5 = bf16[32,128,512]{2,1,0} custom-call(%q), metadata={op_name="jit(sm)/mpi4torch.mla/mpi4torch_paged_latent_attn/pallas_call"}
  %ragged-dot-none = bf16[256,4096]{1,0} custom-call(%a, %b), metadata={op_name="ragged-dot-none"}
  %fusion.9 = bf16[32,19200]{1,0} fusion(%x), kind=kOutput, metadata={op_name="jit(sm)/mpi4torch.serve.decode_step/dot_general"}
"""
PREFILL = """
  %fusion.3 = bf16[1,4096,7680]{2,1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(sm)/mpi4torch.serve.prefill/mpi4torch.mla/dot_general"}
  %fusion.9 = bf16[1,19200]{1,0} fusion(%x), kind=kOutput, metadata={op_name="jit(sm)/mpi4torch.serve.prefill/dot_general"}
"""


def a_trace():
    """Two runs of the decode program around one of the prefill, whose
    instruction names collide, and an install nobody has a text of."""
    ev = [("fusion.3", "bf16[32,7680]", 0, 10),
          ("mpi4torch_paged_latent_attn.5", "bf16[32,128,512]", 10, 40),
          ("ragged-dot-none", "bf16[256,4096]", 40, 60),
          ("fusion.9", "bf16[32,19200]", 60, 70),
          ("fusion.3", "bf16[1,4096,7680]", 100, 400),
          ("fusion.9", "bf16[1,19200]", 400, 410),
          ("scatter.1", "bf16[2048,128,1,640]", 500, 520),
          ("fusion.3", "bf16[32,7680]", 600, 610),
          ("fusion.9", "bf16[32,19200]", 610, 620)]
    modules = [("jit_sm(11)", 0, 70), ("jit_sm(22)", 100, 410),
               ("jit_per_rank(33)", 500, 520), ("jit_sm(11)", 600, 620)]
    trace = Trace(devices={0: DeviceTrace(
        sync=[(n, a, b) for n, _, a, b in ev])})
    return trace, modules, [(n, t) for n, t, _, _ in ev]


def test_events_are_named_after_the_program_whose_run_they_lie_in():
    trace, modules, heads = a_trace()
    texts = {"decode": DECODE, "prefill.4096": PREFILL}
    named = serve_family.name_programs(trace, modules, heads, texts)
    assert {m: p for m, (p, _, _) in named.items()} == {
        "jit_sm(11)": "decode", "jit_sm(22)": "prefill.4096",
        "jit_per_rank(33)": "other"}
    names = [n for n, _, _ in trace.devices[0].sync]
    assert names[0] == "decode:fusion.3" and names[4] == "prefill.4096:fusion.3"
    assert names[6] == "other:scatter.1" and names[7] == "decode:fusion.3"
    scopes = serve_family.program_scopes(texts, fam)
    assert scopes["decode:fusion.3"][0] == "moe"
    assert scopes["prefill.4096:fusion.3"][0] == "mla"
    assert scopes["decode:ragged-dot-none"] == ("moe", "ragged-dot")
    assert "decode:fusion.9" not in scopes
    rec = common.Record(trace=trace, extras={"op_scopes": scopes})
    # busy 420: mla 30 + 300, moe 10 + 20 + 10, the rest under no scope
    assert scope_time_share.read(rec, {"scope": "mla"}) \
        == pytest.approx(100 * 330 / 420)
    assert scope_time_share.read(rec, {"scope": "moe"}) \
        == pytest.approx(100 * 40 / 420)


def test_a_trace_that_does_not_line_up_names_nothing():
    trace, modules, heads = a_trace()
    before = list(trace.devices[0].sync)
    assert serve_family.name_programs(trace, [], heads, {"d": DECODE}) == {}
    assert serve_family.name_programs(trace, modules, heads[:-1],
                                      {"d": DECODE}) == {}
    assert trace.devices[0].sync == before


# -------------------------------------------------- the family's counts

CFG = harness.load_json(ROOT, "benchmarks", "configs",
                        "openpangu-ultra-moe-718b.json")


def test_the_latent_reads_cost_is_the_issues_arithmetic():
    flop, nbytes = fam.latent_read_cost(CFG, live_pages=1, block_size=128)
    assert nbytes == 163_840
    assert flop == 128 * 128 * (640 + 512) * 2
    assert 200 < flop / nbytes < 240          # under the v5e's ridge


def test_grouped_products_count_held_rows_and_touched_experts():
    rows = [3, 0, 1] + [0] * 13
    (f1, b1), (f2, b2) = fam.grouped_dot_cost(CFG, rows)
    d, f = 7680, 2048
    assert f1 == 2 * 4 * d * 2 * f and f2 == 2 * 4 * f * d
    assert b1 == 2 * (4 * d + 4 * 2 * f + 2 * d * 2 * f)
    assert b2 == 2 * (4 * f + 4 * d + 2 * f * d)


def test_kernel_calls_follow_the_traced_steps_own_records():
    rows = np.zeros((4, 16), int)
    rows[:, 2] = 5
    steps = [
        {"active": 0, "decode_pages_live": 0},                  # empty
        {"active": 30, "decode_pages_live": 700,
         "moe_rows": [("prefill", rows * 100), ("decode", rows)]},
        {"active": 31, "decode_pages_live": 710,
         "moe_rows": [("decode", rows)]}]
    calls = fam.kernel_calls(CFG, steps, 128)
    latent = calls["paged_latent_attn"]
    assert latent["events"] == "mpi4torch_paged_latent_attn"
    assert len(latent["calls"]) == 2 * 5
    assert latent["calls"][0] == fam.latent_read_cost(CFG, 700, 128)
    grouped = calls["moe_grouped_dot.serve"]
    assert len(grouped["calls"]) == 3 * 4 * 2
    assert grouped["events"] == "ragged-dot-none"
    # a program that keeps no counters: nothing to count, no error
    none = fam.kernel_calls(CFG, [{"active": 3}], 128)
    assert none["paged_latent_attn"]["calls"] == []
    ctx = common.Context(root=ROOT, cell={}, cfg=CFG, traffic={}, limits={},
                         peaks={"bf16_flops": 197e12,
                                "hbm_bytes_per_s": 819e9},
                         seed=0, seconds=1, trace=True, rehearse=False,
                         t_start=0.0)
    rec = common.Record(ctx=ctx, extras={"kernel_calls": none},
                        trace=Trace(devices={0: DeviceTrace()}))
    assert kernel_roofline.read(rec, {"kernel": "paged_latent_attn"}) is None


def test_experts_touched_is_the_median_over_steps_and_layers():
    rows = np.zeros((3, 4, 16), int)
    rows[0, :, :10] = 1
    rows[1, :, :9] = 2
    rows[2, :, :12] = 1
    rec = common.Record(extras={"routing": {"decode": rows}})
    assert experts_touched.read(rec, {"rows": "decode"}) == 10.0
    assert experts_touched.read(rec, {"rows": "prefill"}) is None
    assert experts_touched.read(common.Record(), {"rows": "decode"}) is None
    assert serve_family.routing_of([
        {"moe_rows": [("prefill", rows[0]), ("decode", rows[1])]},
        {"active": 3}, {"moe_rows": [("decode", rows[2])]}
    ])["decode"].shape == (2, 4, 16)
    assert serve_family.routing_of(None) == {}
