"""The cell ``nemotron-3-super-120b-a12b.serve_ssm_chat``: it rehearses on
the CPU and is correct; with its tokens broken underneath, with the
float8 control in the program's place, or with a program that drops the
carried state (every decode step starts its Mamba-2 layers from ``H = 0``
and an empty convolution tail), it is not; its cycle is the one the issue
states; the new metrics name readers that exist and list the cell; the
family's counts agree with a hand count on a recorded step."""

import json
import os
import subprocess
import sys
from collections import Counter

import pytest

from benchmarks import common, flops, run as harness, trace_reduce
from benchmarks.families import nemotron_h as fam
from benchmarks.readers import scope_roofline, \
    step_count_ratio_where_counted
from benchmarks.tests.test_harness import bench

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "nemotron-3-super-120b-a12b.serve_ssm_chat"
NEW_METRICS = {"ssm_time_share.serve": "serve_tok_s",
               "ssm_state_update_roofline": "serve_tok_s",
               "ssm_scan_roofline": "ttft_p50_ms",
               "ssm_states_touched_per_live": "serve_tok_s"}
CFG = harness.load_json(ROOT, "benchmarks", "configs",
                        "nemotron-3-super-120b-a12b.json")
ARGS = ("--workload", CELL, "--seed", "2147483659", "--seconds", "1",
        "--trace", "0", "--rehearse")

# The program with its carried state dropped, outside the harness: a
# decode step's scan starts from zeros, whatever the slot kept.
DROPPED_STATE = """
import sys
sys.path.insert(0, {root!r})
import jax
import jax.numpy as jnp
from mpi4torch_tpu.models import transformer as T
from mpi4torch_tpu.serve import kv

def from_nothing(spec, p, xBC, dt, entry=None):
    if entry is not None:
        entry = jax.tree.map(jnp.zeros_like, entry)
    return T.mamba2_scan(spec, p, xBC, dt, entry)

kv.mamba2_scan = from_nothing
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


def test_a_program_that_drops_the_carried_state_is_not_correct():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-c",
         DROPPED_STATE.format(root=ROOT, args=list(ARGS))],
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
                           "serve_ssm_chat.json")
    cycle = [tuple(c) for c in tr["cycle"]]
    assert len(cycle) == tr["clients"] == tr["engine"]["slots"] == 128
    assert Counter(p for p, _ in cycle) == {256: 32, 512: 64, 1024: 32}
    for prompt, n in {256: 8, 512: 16, 1024: 8}.items():
        assert Counter(b for p, b in cycle if p == prompt) \
            == {256: n, 384: 2 * n, 512: n}
    assert sum(p for p, _ in cycle) == 73728
    assert sum(n for _, n in cycle) == 49152
    assert all(cycle[i][0] != cycle[i - 1][0] for i in range(128))
    eng = tr["engine"]
    assert (eng["block_size"], eng["num_blocks"], eng["max_new"]) \
        == (128, 2048, 512)
    assert eng["num_blocks"] * eng["block_size"] \
        == 128 * CFG["max_position_embeddings"]
    assert eng["prefill_chunk"] is None and eng["prefix_cache"] is False
    assert eng["temperature"] == 0.0 and eng["eos"] is None
    assert max(p + n for p, n in cycle) <= CFG["max_position_embeddings"]
    assert (tr["stagger_steps"], tr["check_requests"],
            tr["trace_seconds"], tr["kind"]) == (2, 3, 8, "serve_family")
    small = harness.merged(tr, tr["rehearsal"])
    chunk = CFG["rehearsal"]["chunk_size"]
    assert min(p for p, _ in small["cycle"]) > 2 * chunk
    assert small["engine"]["prefix_cache"] is False


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_a_new_metric_names_a_reader_and_lists_the_cell(metric):
    spec = harness.load_json(ROOT, "BENCHMARK.json")
    entry = harness.by_name(spec["per_layer"], metric, "metric")
    assert entry["workloads"][0] == CELL        # later cells may follow
    assert entry["moves"] == NEW_METRICS[metric]
    args = harness.load_json(ROOT, "benchmarks", "metrics", metric + ".json")
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "readers", args["reader"] + ".py"))


def test_the_cell_reports_what_the_other_serving_cells_report():
    spec = harness.load_json(ROOT, "BENCHMARK.json")
    names = [w["name"] for w in spec["workloads"]]
    serving = [w["name"] for w in spec["workloads"][:names.index(CELL)]
               if w["traffic"].startswith("serve_")]
    assert len(serving) == 4
    expert = ("moe_time_share.serve", "moe_grouped_dot_roofline.serve",
              "moe_experts_touched.serve", "decode_pages_read_per_live",
              "decode_grid_steps_per_live_page")
    for m in spec["end_to_end"] + spec["per_layer"]:
        listed = m.get("workloads", ())
        if all(c in listed for c in serving) or m["name"] in expert:
            assert CELL in listed, m["name"]
        elif m["name"] not in NEW_METRICS:
            assert CELL not in listed, m["name"]
    assert set(CFG["reduced"]) == set(CFG["published"])
    assert not any("dim" in k or "rank" in k or "size" in k.replace(
        "vocab_size", "") for k in CFG["reduced"])
    cell = harness.by_name(spec["workloads"], CELL, "workload")
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert harness.by_name(spec["configs"], cell["config"],
                           "configuration")["reduced"] == CFG["reduced"]


def test_the_file_holds_the_catalogs_numbers():
    """Every top-level number of the published config, but the keys
    listed as reduced, is in the file as published."""
    published = {
        "hidden_size": 4096, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128, "chunk_size": 128,
        "conv_kernel": 4, "expand": 2, "mamba_head_dim": 64,
        "mamba_num_heads": 128, "n_groups": 8, "ssm_state_size": 128,
        "intermediate_size": 2688, "moe_intermediate_size": 2688,
        "moe_latent_size": 1024,
        "moe_shared_expert_intermediate_size": 5376,
        "num_experts_per_tok": 22, "n_shared_experts": 1, "n_group": 1,
        "topk_group": 1, "routed_scaling_factor": 5,
        "layer_norm_epsilon": 1e-5, "norm_eps": 1e-5,
        "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "rope_theta": 10000,
        "partial_rotary_factor": 1, "num_logits_to_keep": 1}
    for k, v in published.items():
        assert CFG[k] == v and k not in CFG["reduced"], k
    assert CFG["published"] == {
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "vocab_size": 131072, "max_position_embeddings": 262144,
        "num_nextn_predict_layers": 1, "mtp_hybrid_override_pattern": "*E",
        "hybrid_override_pattern": CFG["published"][
            "hybrid_override_pattern"]}
    whole = CFG["published"]["hybrid_override_pattern"]
    assert len(whole) == 88 and Counter(whole) == {"M": 40, "E": 40, "*": 8}
    assert CFG["deployment_share"]["layers"] == list(range(26, 37))
    assert CFG["hybrid_override_pattern"] == whole[26:37] == "EMEMEMEMEM*"
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"],
            CFG["vocab_size"], CFG["max_position_embeddings"],
            CFG["num_nextn_predict_layers"]) == (11, 128, 32768, 2048, 0)
    assert CFG["deployment_share"]["chips_per_layer"] == 4
    assert len(CFG["assumed"]) >= 7


def _record(steps, extras=None):
    ctx = common.Context(root=ROOT, cell={}, cfg=CFG, traffic={}, limits={},
                         peaks={}, seed=0, seconds=1, trace=True,
                         rehearse=False, t_start=0.0)
    return common.Record(ctx=ctx, scalars={"setup_s": 0.0, "window_s": 1.0},
                         extras={"step_log": {"cap": 8192,
                                              "records": steps},
                                 **(extras or {})})


def test_touched_per_live_reads_the_steps_two_counters():
    args = harness.load_json(ROOT, "benchmarks", "metrics",
                             "ssm_states_touched_per_live.json")
    step = lambda t, touched, live: {
        "t0_ns": t, "t1_ns": t + 1, "prefill_tokens": 0, "active": 128,
        "ssm_states_touched": touched, "ssm_states_live": live}
    rec = _record([step(10, 640, 640), step(20, 640, 320)])
    assert step_count_ratio_where_counted.read(rec, args) \
        == pytest.approx(1280 / 960)
    # A step of a program that keeps no such count, or counted nothing.
    bare = {"t0_ns": 30, "t1_ns": 31, "prefill_tokens": 0, "active": 4}
    assert step_count_ratio_where_counted.read(
        _record([step(10, 640, 640), bare]), args) is None
    assert step_count_ratio_where_counted.read(
        _record([step(10, 0, 0)]), args) is None


def test_kernel_calls_agree_with_a_hand_count_on_a_recorded_step():
    """A decode step of 128 live slots: one state update over 5 x 128
    states of 128 x 64 x 128 float32 read and written once beside their
    3 x 10,240 bfloat16 convolution inputs; a step that admitted a
    512-token prompt: five scans; two grouped products for each of the
    five expert layers' rows, in the latent."""
    rows = [[0] * 128 for _ in range(5)]
    for layer in rows:
        layer[2] = 3
    steps = [{"active": 128, "ssm_states_live": 640,
              "ssm_states_touched": 640, "prefill_tokens": 0,
              "moe_rows": [("decode", rows)]},
             {"active": 0, "ssm_states_live": 0, "prefill_tokens": 512,
              "moe_rows": [("prefill", rows)]}]
    calls = fam.kernel_calls(CFG, steps, 128)
    update = calls["ssm_state_update"]
    state = 128 * 64 * 128
    assert update["calls"] == [
        (6 * 640 * state, 640 * (2 * state * 4 + 2 * 3 * 10240 * 2))]
    assert update["scope"] == "mpi4torch.ssm_update"
    scan = calls["ssm_scan"]
    assert scan["scope"] == "mpi4torch.ssm_scan" and len(scan["calls"]) == 5
    assert scan["calls"][0] == (
        512 * 6_553_600, 512 * (2 * 8192 + 2 * 1024 + 128) * 2)
    assert fam.scan_cost(CFG, 1)[0] \
        == 2 * 128 * (128 * 8 + 64 * 128) + 4 * 128 * 64 * 128
    grouped = calls["moe_grouped_dot.serve"]["calls"]
    assert len(grouped) == 2 * 5 * 2
    lat, f = CFG["moe_latent_size"], CFG["moe_intermediate_size"]
    assert grouped[0] == (2 * 3 * lat * f, 2 * (3 * lat + 3 * f + lat * f))
    assert grouped[1] == (2 * 3 * f * lat, 2 * (3 * f + 3 * lat + f * lat))
    scopes = fam.scopes()
    assert scopes["ssm"] == "mpi4torch.ssm/" and scopes["moe"] \
        == "mpi4torch.moe" and not any(k.startswith("ssm_") for k in scopes)
    # 0.7 FLOP a byte: memory bound at the chip's peaks.
    peaks = harness.load_json(ROOT, "benchmarks", "peaks.json")["TPU v5 lite"]
    assert flops.least_seconds(*update["calls"][0], peaks)[1] == "memory"


def test_the_scopes_separator_keeps_a_nested_scopes_name():
    """An instruction under ``mpi4torch.ssm/mpi4torch.ssm_update`` is the
    whole mixer's for the time share, and what it computes names the
    nested scope for the roofline."""
    from benchmarks.traffic_kinds.train_family import op_scopes

    text = "\n".join([
        '  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, metadata={'
        'op_name="jit(sm)/mpi4torch.serve.decode_step/mpi4torch.ssm/'
        'mpi4torch.ssm_update/mpi4torch_ssd_update/pallas_call"}',
        '  %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, metadata={'
        'op_name="jit(sm)/mpi4torch.serve.decode_step/mpi4torch.ssm/'
        'dot_general"}',
        '  %fusion.6 = f32[8]{0} fusion(%p), kind=kLoop, metadata={'
        'op_name="jit(sm)/mpi4torch.serve.decode_step/mpi4torch.moe/'
        'dot_general"}'])
    found = op_scopes(text, fam.scopes(), fam.KERNELS)
    assert found["fusion.4"][0] == found["fusion.5"][0] == "ssm"
    assert "mpi4torch.ssm_update" in found["fusion.4"][1]
    assert "mpi4torch.ssm_update" not in found["fusion.5"][1]
    assert found["fusion.6"][0] == "moe"


def test_the_scope_roofline_times_every_event_under_the_scope():
    """Every event under the scope, each with its own time (a loop and
    its body are not counted twice), against the counted calls' least
    time; without the programs' scopes, without an event under the scope
    or without a counted call nothing is reported."""
    us = 1000
    dev = trace_reduce.DeviceTrace()
    dev.sync = [
        ("decode:fusion.9", 0, 30 * us),
        ("decode:mpi4torch_ssd_update.5", 30 * us, 1030 * us),
        ("decode:while.3", 1100 * us, 1300 * us),
        ("decode:fusion.10", 1150 * us, 1250 * us),
        ("decode:fusion.77", 1400 * us, 2000 * us)]
    trace = trace_reduce.Trace()
    trace.devices = {0: dev}
    under = "mpi4torch.ssm_update"
    scopes = {"decode:fusion.9": ("ssm", under + "/mul"),
              "decode:mpi4torch_ssd_update.5": ("ssm", under
                                                + "/pallas_call"),
              "decode:while.3": ("ssm", under + "/while"),
              "decode:fusion.10": ("ssm", under + "/while/body/add"),
              "decode:fusion.77": ("ssm", "dot_general")}
    cost = fam.state_update_cost(CFG, 128)
    calls = {"ssm_state_update": {"scope": under, "calls": [cost]}}
    rec = _record([], {"kernel_calls": calls, "op_scopes": scopes})
    rec.trace = trace
    rec.ctx.peaks.update(harness.load_json(
        ROOT, "benchmarks", "peaks.json")["TPU v5 lite"])
    args = {"reader": "scope_roofline", "kernel": "ssm_state_update"}
    least = flops.least_seconds(*cost, rec.ctx.peaks)[0]
    assert scope_roofline.read(rec, args) == pytest.approx(
        100.0 * least / 1230e-6)
    calls["ssm_state_update"]["calls"] = []
    assert scope_roofline.read(rec, args) is None
    calls["ssm_state_update"]["calls"] = [cost]
    calls["ssm_state_update"]["scope"] = "mpi4torch.ssm_scan"
    assert scope_roofline.read(rec, args) is None
    rec.extras.pop("op_scopes")
    assert scope_roofline.read(rec, args) is None
