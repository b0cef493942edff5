"""The cell ``glm-5.2.serve_dsa_16k``: it rehearses on the CPU and is
correct; with its tokens broken underneath, with the float8 control in
the program's place, or with the program's selection patched to the most
recent rows in the place of the scored ones, it is not; its cycle is the
one the issue states; the new metrics name readers that exist and list
the cell; the family's counts agree with a hand count on a recorded
step."""

import json
import os
import subprocess
import sys
from collections import Counter

import numpy as np
import pytest

from benchmarks import common, run as harness
from benchmarks.families import glm_dsa as fam
from benchmarks.readers import kernel_roofline_scoped, step_count_ratio
from benchmarks.tests.test_harness import bench

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "glm-5.2.serve_dsa_16k"
NEW_METRICS = ["dsa_time_share.serve", "dsa_rows_read_share.serve",
               "paged_sparse_latent_attn_roofline",
               "paged_index_score_roofline"]
CFG = harness.load_json(ROOT, "benchmarks", "configs", "glm-5.2.json")
ARGS = ("--workload", CELL, "--seed", "2147483659", "--seconds", "1",
        "--trace", "0", "--rehearse")

# The program with its selection patched, outside the harness: every
# query attends the most recent index_topk positions.
RECENT_ROWS = """
import sys
sys.path.insert(0, {root!r})
import jax.numpy as jnp
from mpi4torch_tpu.models import transformer as T
from mpi4torch_tpu.serve import kv

def recent_mask(q_i, k_i, w, top_k, q_offset=0):
    t = q_offset + jnp.arange(q_i.shape[0])[:, None]
    s = jnp.arange(k_i.shape[0])[None, :]
    return (s <= t) & (s > t - top_k)

def recent_rows(scores, valid, top_k):
    return T.select_rows(jnp.broadcast_to(jnp.arange(
        scores.shape[-1], dtype=jnp.float32), scores.shape), valid, top_k)

kv.index_select_mask, kv.select_rows = recent_mask, recent_rows
from benchmarks import run as harness
sys.exit(harness.main({args!r}))
"""


def rehearse(*more):
    return bench(ROOT, *ARGS, *more)


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


def test_a_program_that_selects_the_most_recent_rows_is_not_correct():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-c",
         RECENT_ROWS.format(root=ROOT, args=list(ARGS))],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = p.stdout.strip().splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is False and result["failed"] == 0
    for name in ("served_logit_gap", "served_logit_gap_mean"):
        assert any(l.startswith(f"compared {name}:")
                   and l.endswith("NOT OK") for l in out)
    assert any(l.startswith("compared prefix_hits:") and l.endswith(" ok")
               for l in out)


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
                           "serve_dsa_16k.json")
    cycle = [tuple(c) for c in tr["cycle"]]
    assert len(cycle) == tr["clients"] == tr["engine"]["slots"] == 16
    assert Counter(p for p, _ in cycle) == {4096: 4, 8192: 8, 16384: 4}
    for prompt, (a, b, c) in {4096: (1, 2, 1), 8192: (2, 4, 2),
                              16384: (1, 2, 1)}.items():
        assert Counter(n for p, n in cycle if p == prompt) \
            == {576: a, 768: b, 960: c}
    assert sum(p for p, _ in cycle) == 147456
    assert sum(n for _, n in cycle) == 12288
    assert all(cycle[i][0] != cycle[i - 1][0] for i in range(16))
    eng = tr["engine"]
    assert (eng["block_size"], eng["num_blocks"], eng["max_new"]) \
        == (128, 2176, 960)
    assert eng["num_blocks"] * eng["block_size"] \
        == 16 * CFG["max_position_embeddings"]
    assert eng["prefill_chunk"] is None and eng["prefix_cache"] is True
    assert max(p + n for p, n in cycle) <= CFG["max_position_embeddings"]
    assert min(p for p, _ in cycle) > CFG["index_topk"]
    assert (tr["stagger_steps"], tr["check_requests"],
            tr["trace_seconds"]) == (5, 3, 8)
    small = harness.merged(tr, tr["rehearsal"])
    top_k = CFG["rehearsal"]["index_topk"]
    assert min(p for p, _ in small["cycle"]) > top_k


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_metric_names_a_reader_and_lists_the_cell(metric):
    spec = harness.load_json(ROOT, "BENCHMARK.json")
    entry = harness.by_name(spec["per_layer"], metric, "metric")
    assert CELL in entry["workloads"] and entry["moves"] == "serve_tok_s"
    args = harness.load_json(ROOT, "benchmarks", "metrics", metric + ".json")
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "readers", args["reader"] + ".py"))


def test_the_cell_reports_what_the_other_latent_cells_report():
    spec = harness.load_json(ROOT, "BENCHMARK.json")
    other = "openpangu-ultra-moe-718b.serve_latent_4k"
    # The dense latent kernel does not run here: its roofline is the
    # one metric of that cell this one leaves out.
    for m in spec["end_to_end"] + spec["per_layer"]:
        if other in m.get("workloads", ()) \
                and m["name"] != "paged_latent_attn_roofline":
            assert CELL in m["workloads"], m["name"]
    assert set(CFG["reduced"]) == set(CFG["published"])
    assert not any("dim" in k or "rank" in k or "size" in k.replace(
        "vocab_size", "") for k in CFG["reduced"])
    cell = harness.by_name(spec["workloads"], CELL, "workload")
    assert cell["chips"] == 1 and len(cell["why"]) <= 200


def test_the_file_holds_the_catalogs_numbers():
    """Every top-level number of the published config, but the keys
    listed as reduced, is in the file as published."""
    published = {
        "hidden_size": 6144, "num_attention_heads": 64, "q_lora_rank": 2048,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
        "qk_head_dim": 256, "v_head_dim": 256, "head_dim": 192,
        "index_n_heads": 32, "index_head_dim": 128, "index_topk": 2048,
        "index_topk_freq": 4, "index_skip_topk_offset": 3,
        "intermediate_size": 12288, "moe_intermediate_size": 2048,
        "num_experts_per_tok": 8, "n_shared_experts": 1,
        "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-5,
        "num_key_value_heads": 64, "n_group": 1, "topk_group": 1,
        "moe_layer_freq": 1, "ep_size": 1}
    for k, v in published.items():
        assert CFG[k] == v and k not in CFG["reduced"], k
    assert CFG["rope_parameters"] == {"rope_theta": 8000000,
                                      "rope_type": "default"}
    assert CFG["published"]["num_hidden_layers"] == 78
    assert CFG["deployment_share"]["layers"] == [2, 3, 4, 5, 6]
    assert CFG["indexer_types"] == ["full", "shared", "shared", "shared",
                                    "full"]


def _record(steps, extras=None):
    ctx = common.Context(root=ROOT, cell={}, cfg=CFG, traffic={}, limits={},
                         peaks={}, seed=0, seconds=1, trace=True,
                         rehearse=False, t_start=0.0)
    return common.Record(ctx=ctx, scalars={"setup_s": 0.0, "window_s": 1.0},
                         extras={"step_log": {"cap": 8192,
                                              "records": steps},
                                 **(extras or {})})


def test_the_rows_read_share_reads_the_steps_two_counters():
    args = harness.load_json(ROOT, "benchmarks", "metrics",
                             "dsa_rows_read_share.serve.json")
    step = lambda t, read, live: {
        "t0_ns": t, "t1_ns": t + 1, "prefill_tokens": 0, "active": 16,
        "dsa_rows_read": read, "dsa_rows_live": live}
    rec = _record([step(10, 5 * 16 * 2048, 5 * 16 * 8192),
                   step(20, 5 * 16 * 2048, 5 * 16 * 12288)])
    assert step_count_ratio.read(rec, args) == pytest.approx(20.0)
    assert step_count_ratio.read(_record([step(10, 0, 0)]), args) is None


def test_kernel_calls_agree_with_a_hand_count_on_a_recorded_step():
    """A decode step of 16 live slots at positions 4,200-17,000: five
    sparse reads of min(pos + 1, 2048) rows a slot, two scorings of
    pos + 1 rows a slot, two grouped products for each of the four
    expert layers' rows."""
    pos = np.linspace(4200, 17000, 16).astype(int)
    live_rows = int((pos + 1).sum())
    rows = np.zeros((4, 16), int)
    rows[:, 2] = 1
    steps = [{"active": 16, "dsa_rows_live": 5 * live_rows,
              "dsa_rows_read": 5 * 16 * 2048,
              "dsa_rows_scored": 2 * live_rows,
              "moe_rows": [("decode", rows)]},
             {"active": 0, "moe_rows": [("prefill", rows)]}]
    calls = fam.kernel_calls(CFG, steps, 128)
    sparse = calls["paged_sparse_latent_attn"]
    assert len(sparse["calls"]) == 5 == CFG["num_hidden_layers"]
    assert sparse["calls"][0] == (
        16 * 2048 * 64 * (640 + 512) * 2, 16 * 2048 * 1280)
    assert sparse["events"] == "mpi4torch_paged_sparse_latent_attn"
    assert sparse["beside_scope"] == "mpi4torch.paged_sparse_gather"
    scoring = calls["paged_index_score"]
    assert len(scoring["calls"]) == 2
    assert scoring["calls"][0] == (live_rows * 32 * 128 * 2, live_rows * 256)
    assert scoring["events"] == "mpi4torch_paged_index_score"
    grouped = calls["moe_grouped_dot.serve"]["calls"]
    assert len(grouped) == 2 * 4 * 2
    d, f = CFG["hidden_size"], CFG["moe_intermediate_size"]
    assert grouped[0] == (2 * d * 2 * f, 2 * (d + 2 * f + d * 2 * f))
    assert fam.scopes()["dsa"] == "mpi4torch.dsa"
    assert fam.sparse_read_cost(CFG, 1) == (64 * 1152 * 2, 1280)
    assert fam.index_score_cost(CFG, 1) == (32 * 128 * 2, 256)


def test_the_scoped_roofline_times_the_kernel_and_the_gather_that_feeds_it():
    """The kernel's own events and every event under the gather's scope
    are the read's time; without the programs' scopes nothing is
    reported."""
    from benchmarks import trace_reduce

    us = 1000
    dev = trace_reduce.DeviceTrace()
    dev.sync = [
        ("decode:fusion.9", 0, 30 * us),
        ("decode:mpi4torch_paged_sparse_latent_attn.5", 30 * us, 50 * us),
        ("decode:fusion.10", 60 * us, 90 * us),
        ("decode:mpi4torch_paged_sparse_latent_attn.6", 90 * us, 110 * us),
        ("decode:fusion.77", 110 * us, 500 * us)]
    trace = trace_reduce.Trace()
    trace.devices = {0: dev}
    scopes = {"decode:fusion.9": ("mla", "mpi4torch.paged_sparse_gather/gather"),
              "decode:fusion.10": ("mla", "mpi4torch.paged_sparse_gather/gather"),
              "decode:fusion.77": ("mla", "dot_general")}
    cost = fam.sparse_read_cost(CFG, 16 * 2048)
    calls = {"paged_sparse_latent_attn": {
        "events": "mpi4torch_paged_sparse_latent_attn",
        "calls": [cost, cost],
        "beside_scope": "mpi4torch.paged_sparse_gather"}}
    rec = _record([], {"kernel_calls": calls, "op_scopes": scopes})
    rec.trace = trace
    rec.ctx.peaks.update(harness.load_json(
        ROOT, "benchmarks", "peaks.json")["TPU v5 lite"])
    args = {"reader": "kernel_roofline_scoped",
            "kernel": "paged_sparse_latent_attn"}
    from benchmarks import flops
    least = 2 * flops.least_seconds(*cost, rec.ctx.peaks)[0]
    assert kernel_roofline_scoped.read(rec, args) == pytest.approx(
        100.0 * least / 100e-6)
    rec.extras.pop("op_scopes")
    assert kernel_roofline_scoped.read(rec, args) is None
