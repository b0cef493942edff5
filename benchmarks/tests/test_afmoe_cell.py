"""The cell ``trinity-mini.serve_swa_mix_16k``: it rehearses on the CPU and
is correct; with its tokens broken underneath, with the float8 control in
the program's place, with the window dropped (sliding layers that read
every position) or with rotation put on the full layer, it is not; its
cycle is the one the issue states; the new metrics name readers that
exist and list the cell; the cell is in every list it was appended to;
the family's counts agree with a hand count on a recorded step."""

import json
import os
import subprocess
import sys
from collections import Counter

import pytest

from benchmarks import common, flops, run as harness
from benchmarks.families import afmoe as fam
from benchmarks.readers import kernel_roofline, \
    step_count_ratio_where_counted
from benchmarks.tests.test_harness import bench

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "trinity-mini.serve_swa_mix_16k"
SIBLING = "nemotron-3-super-120b-a12b.serve_ssm_chat"
NEW_METRICS = {"attn_time_share.serve": "serve_tok_s",
               "attn_window_time_share.serve": "serve_tok_s",
               "window_pages_held_per_slot": "serve_tok_s",
               "paged_attn_roofline.serve": "serve_tok_s"}
CFG = harness.load_json(ROOT, "benchmarks", "configs", "trinity-mini.json")
ARGS = ("--workload", CELL, "--seed", "2147483659", "--seconds", "1",
        "--trace", "0", "--rehearse")

# The program with one thing about its attention changed, outside the
# harness: the family's configuration is made, then every mixer is put
# through ``change``.
PROBE = """
import dataclasses, sys
sys.path.insert(0, {root!r})
from benchmarks.families import afmoe as fam

change = {change}
made = fam.transformer_config

def changed(cfg, remat=False):
    tcfg = made(cfg, remat)
    return dataclasses.replace(tcfg, layers=tuple(
        dataclasses.replace(s, mixer=change(s.mixer)) for s in tcfg.layers))

fam.transformer_config = changed
from benchmarks import run as harness
sys.exit(harness.main({args!r}))
"""
# Sliding layers that read every position (one class of pages then).
NO_WINDOW = "lambda m: dataclasses.replace(m, window=0)"
# A full layer that rotates its queries and keys as a sliding one does.
ROPE_ON_FULL = "lambda m: m if m.window else dataclasses.replace(m, rope=True)"


def rehearse(*more):
    return bench(ROOT, *ARGS, *more)


def probe(change: str):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-c",
         PROBE.format(root=ROOT, change=change, args=list(ARGS))],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return p.stdout.strip().splitlines()


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


@pytest.mark.parametrize("change", [NO_WINDOW, ROPE_ON_FULL],
                         ids=["no_window", "rope_on_full"])
def test_a_program_that_attends_otherwise_is_not_correct(change):
    out = probe(change)
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
                           "serve_swa_mix_16k.json")
    cycle = [tuple(c) for c in tr["cycle"]]
    assert len(cycle) == tr["clients"] == tr["engine"]["slots"] == 64
    assert Counter(p for p, _ in cycle) == {1024: 16, 4096: 32, 16384: 16}
    for prompt, n in {1024: 4, 4096: 8, 16384: 4}.items():
        assert Counter(b for p, b in cycle if p == prompt) \
            == {512: n, 768: 2 * n, 1024: n}
    assert sum(p for p, _ in cycle) == 409600
    assert sum(n for _, n in cycle) == 49152
    assert all(p == 4096 for p, _ in cycle[::2])
    assert all(cycle[i][0] != cycle[i - 1][0] for i in range(64))
    eng = tr["engine"]
    positions, bs = CFG["max_position_embeddings"], eng["block_size"]
    assert (bs, eng["max_new"], positions) == (128, 1024, 17408)
    assert eng["num_blocks"] == 64 * positions // bs == 8704
    # The 17 pages a window of 2,048 touches, and one to spare, a slot.
    touched = (CFG["sliding_window"] + 2 * bs - 2) // bs
    assert touched == 17 and eng["window_blocks"] == 64 * (touched + 1)
    assert eng["prefill_chunk"] is None and eng["prefix_cache"] is False
    assert eng["temperature"] == 0.0 and eng["eos"] is None
    assert max(p + n for p, n in cycle) == positions
    assert (tr["stagger_steps"], tr["check_requests"],
            tr["trace_seconds"], tr["kind"]) == (3, 3, 8, "serve_family")
    # One class for all five layers would not fit beside the weights.
    page = bs * 4 * 128 * 2 * 2
    assert (eng["num_blocks"] + 4 * eng["window_blocks"]) * page < 3.5e9 \
        < 11.4e9 < 5 * eng["num_blocks"] * page
    small = harness.merged(tr, tr["rehearsal"])
    window = CFG["rehearsal"]["sliding_window"]
    assert window == 2 * small["engine"]["block_size"]
    assert min(p for p, _ in small["cycle"]) > window
    assert small["engine"]["prefix_cache"] is False


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_a_new_metric_names_a_reader_and_lists_the_cell(metric):
    spec = harness.load_json(ROOT, "BENCHMARK.json")
    entry = harness.by_name(spec["per_layer"], metric, "metric")
    assert CELL in entry["workloads"]           # by name: cells may follow
    assert entry["moves"] == NEW_METRICS[metric]
    args = harness.load_json(ROOT, "benchmarks", "metrics", metric + ".json")
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "readers", args["reader"] + ".py"))


def test_the_cell_is_in_every_list_it_was_appended_to():
    """Every list the newest serving sibling is on, but those of its
    state-space layers; the flash roofline's reader takes no serving
    family's calls and keeps its own list."""
    spec = harness.load_json(ROOT, "BENCHMARK.json")
    skipped = {"ssm_time_share.serve", "ssm_state_update_roofline",
               "ssm_scan_roofline", "ssm_states_touched_per_live"}
    named = ("moe_time_share.serve", "moe_grouped_dot_roofline.serve",
             "moe_experts_touched.serve", "moe_overflow_calls.serve",
             "decode_grid_steps_per_live_page",
             "decode_pages_read_per_live", "serve_tok_s", "ttft_p50_ms",
             "tok_gap_p99_ms")
    on = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]
          if CELL in m.get("workloads", ())}
    assert set(named) <= on and set(NEW_METRICS) <= on
    for m in spec["end_to_end"] + spec["per_layer"]:
        listed = m.get("workloads", ())
        if m["name"] in skipped or m["name"] == "flash_fwd_roofline.serve":
            assert CELL not in listed, m["name"]
        elif SIBLING in listed:
            assert CELL in listed, m["name"]
    cell = harness.by_name(spec["workloads"], CELL, "workload")
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    config = harness.by_name(spec["configs"], cell["config"],
                             "configuration")
    assert config["reduced"] == CFG["reduced"] and len(config["why"]) <= 200
    assert config["source"] == CFG["source"]


def test_the_file_holds_the_catalogs_numbers():
    """Every top-level number of the published config, but the keys
    listed as reduced, is in the file as published; no width is among
    the reduced."""
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128,
        "intermediate_size": 6144, "moe_intermediate_size": 1024,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_shared_experts": 1, "sliding_window": 2048,
        "vocab_size": 200192, "global_attn_every_n_layers": 4,
        "n_group": 1, "topk_group": 1, "num_expert_groups": 1,
        "num_limited_groups": 1, "route_scale": 2.826,
        "rms_norm_eps": 1e-5, "rope_theta": 10000,
        "load_balance_coeff": 0.001}
    for k, v in published.items():
        assert CFG[k] == v and k not in CFG["reduced"], k
    assert CFG["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types", "max_position_embeddings"]
    assert set(CFG["reduced"]) == set(CFG["published"])
    assert not any("dim" in k or "rank" in k or "size" in k
                   for k in CFG["reduced"])
    whole = CFG["published"]["layer_types"]
    assert len(whole) == 32 and Counter(whole) == {
        "sliding_attention": 24, "full_attention": 8}
    kept = CFG["deployment_share"]["layers"]
    assert kept == [1, 4, 5, 6, 7]
    assert CFG["layer_types"] == [whole[i] for i in kept] \
        == ["sliding_attention"] * 4 + ["full_attention"]
    assert (CFG["num_hidden_layers"], CFG["num_dense_layers"],
            CFG["max_position_embeddings"]) == (5, 1, 17408)
    assert (CFG["published"]["num_hidden_layers"],
            CFG["published"]["num_dense_layers"],
            CFG["published"]["max_position_embeddings"]) == (32, 2, 131072)
    share = CFG["deployment_share"]
    assert (share["chips_per_layer"], share["pipeline_stages"]) == (1, 8)
    assert CFG["mup_enabled"] and CFG["route_norm"]
    assert CFG["score_func"] == "sigmoid" and len(CFG["assumed"]) >= 8
    assert sum("not on a file in this sandbox" in a
               for a in CFG["assumed"]) == 5


def test_the_program_is_the_configurations():
    """The family's spec at the published widths: sliding layers rotate
    under a window of 2,048, the full layer does neither; every expert
    held; the embedding's scale."""
    tcfg = fam.transformer_config(CFG)
    assert [(s.mixer.window, s.mixer.rope) for s in tcfg.layers] \
        == [(2048, True)] * 4 + [(0, False)]
    m = tcfg.layers[0].mixer
    assert (m.n_heads, m.n_kv_heads, m.head_dim, m.qk_norm, m.gate) \
        == (32, 4, 128, True, True)
    assert tcfg.layers[0].ffn is None and all(s.post_norm
                                              for s in tcfg.layers)
    e = tcfg.layers[1].ffn
    assert (e.n_experts, e.n_held, e.first_expert, e.top_k, e.d_expert,
            e.n_shared, e.scale, e.score, e.renorm) \
        == (128, 128, 0, 8, 1024, 1, 2.826, "sigmoid", True)
    assert tcfg.embed_scale == 2048 ** 0.5 and not tcfg.pos_table
    scopes = fam.scopes()
    assert list(scopes)[:2] == ["attn_window", "attn"]
    assert scopes["attn"].endswith("/") and "moe" in scopes


def _record(steps, extras=None):
    ctx = common.Context(root=ROOT, cell={}, cfg=CFG, traffic={}, limits={},
                         peaks={}, seed=0, seconds=1, trace=True,
                         rehearse=False, t_start=0.0)
    return common.Record(ctx=ctx, scalars={"setup_s": 0.0, "window_s": 1.0},
                         extras={"step_log": {"cap": 8192,
                                              "records": steps},
                                 **(extras or {})})


def test_window_pages_held_per_slot_reads_the_records():
    """At most the pages a window touches while the manager frees; a
    manager that stops freeing reads the whole row; a program without
    the counters (the parent) reads nothing."""
    args = harness.load_json(ROOT, "benchmarks", "metrics",
                             "window_pages_held_per_slot.json")
    step = lambda held, live: {"t0_ns": 10, "t1_ns": 11,
                               "window_pages_held": held,
                               "window_slots_live": live}
    rec = _record([step(64 * 17, 64), step(60 * 16, 60)])
    assert step_count_ratio_where_counted.read(rec, args) \
        == (64 * 17 + 60 * 16) / 124
    assert step_count_ratio_where_counted.read(
        _record([step(64 * 136, 64)]), args) == 136
    assert step_count_ratio_where_counted.read(
        _record([{"t0_ns": 10, "t1_ns": 11, "active": 4}]), args) is None


def test_the_paged_reads_cost_is_a_hand_count():
    """One decode step at 64 live slots: the sliding layers read what
    the window class holds, the full layer the rest of the live pages;
    a page pair is 262,144 bytes and 2,097,152 FLOP."""
    flop, nbytes = fam.paged_read_cost(CFG, 1, 128)
    assert (flop, nbytes) == (4 * 128 * 32 * 128, 262_144)
    step = {"active": 64, "window_pages_held": 1000,
            "decode_pages_live": 4400, "prefill_tokens": 0,
            "moe_rows": [("decode", [[4] * 128] * 4)]}
    calls = fam.kernel_calls(CFG, [step, {"active": 0}], 128)
    paged = calls["paged_attn"]
    assert paged["events"] == "mpi4torch_paged_attn"
    assert paged["calls"] == [fam.paged_read_cost(CFG, 1000, 128)] * 4 \
        + [fam.paged_read_cost(CFG, 3400, 128)]
    grouped = calls["moe_grouped_dot.serve"]["calls"]
    assert len(grouped) == 2 * 4
    # 512 rows on 128 experts: 2 x 512 x 2048 x 2048 FLOP, the rows in
    # and out and every expert's gate and up matrices once.
    assert grouped[0] == (2 * 512 * 2048 * 2048,
                          2 * (512 * 2048 + 512 * 2048 + 128 * 2048 * 2048))
    # A program without a window class counts no call of the read.
    assert fam.kernel_calls(CFG, [{"active": 64, "decode_pages_live": 9}],
                            128)["paged_attn"]["calls"] == []
    assert kernel_roofline.read(_record([]), {"kernel": "paged_attn"}) is None
    peaks = harness.load_json(ROOT, "benchmarks", "peaks.json")["TPU v5 lite"]
    assert flops.least_seconds(flop, nbytes, peaks)[1] == "memory"
