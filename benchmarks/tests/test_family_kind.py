"""The ``train_family`` kind: the new cell rehearses on the CPU and is
correct; broken underneath, or with the float8 control in the program's
place, it is not; a second family added as new files only is found; the
readers of the scopes' time shares, of the routing counters and of a
family kernel's roofline on recorded tables."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from benchmarks import common, run as harness
from benchmarks.readers import (expert_imbalance, kernel_roofline,
                                scope_time_share)
from benchmarks.tests.test_harness import bench, copy_benchmark
from benchmarks.trace_reduce import DeviceTrace, Trace
from benchmarks.traffic_kinds import train_family

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "kimi-linear-48b-a3b.train_kda_8k"


def rehearse(root, *more, cell=CELL, pythonpath=None):
    return bench(root, "--workload", cell, "--seed", "2147483659",
                 "--seconds", "1", "--trace", "0", "--rehearse", *more,
                 pythonpath=pythonpath)


def scalars_of(err: str) -> dict:
    """The run's scalars: the second object of its ``notes`` line."""
    notes = [l for l in err.splitlines() if l.startswith("notes ")][-1][6:]
    _, end = json.JSONDecoder().raw_decode(notes)
    return json.loads(notes[end:])


def test_the_cell_rehearses_and_is_correct():
    rc, out, err = rehearse(ROOT)
    assert rc == 0, err[-2000:]
    result = json.loads(out[-1])
    assert result["correct"] is True and result["attempted"] > 0
    compared = [l for l in out if l.startswith("compared ")]
    assert len(compared) == 4 and all(l.endswith(" ok") for l in compared)
    notes = [l for l in err.splitlines() if l.startswith("notes ")][-1]
    assert '"moe_rows"' in notes and '"gradient_s"' in notes


@pytest.mark.parametrize("broken,number", [
    ("state_unchanged", "param_change_gap"), ("half_batch", "grad_norm_gap")])
def test_a_broken_step_is_not_correct(broken, number):
    rc, out, err = rehearse(ROOT, "--break", broken)
    assert rc == 0, err[-2000:]
    assert json.loads(out[-1])["correct"] is False
    assert any(l.startswith(f"compared {number}:") and l.endswith("NOT OK")
               for l in out)


def test_the_float8_control_is_not_correct_on_any_seed():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "control.py"),
         "--workload", CELL, "--seeds", "11,12,13", "--seconds", "0",
         "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["control_not_correct_on_every_seed"] is True


def test_a_second_family_is_found_as_new_files_only(tmp_path):
    """A made-up family, ``kimi_twin``: its own module under
    ``families/`` (here the first family's functions under another
    name), a configuration that names it, a cell; no file that was there
    is edited."""
    root = copy_benchmark(tmp_path)
    b = os.path.join(root, "benchmarks")
    before = {}
    for d, _, files in os.walk(b):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()

    def add(rel, text):
        p = os.path.join(b, rel)
        assert not os.path.exists(p)
        with open(p, "w") as f:
            f.write(text)

    add("families/kimi_twin.py", textwrap.dedent("""\
        from benchmarks.families.kimi_linear import *  # noqa: F401,F403
        from benchmarks.families import kimi_linear as _first

        def train_flops_per_token(cfg, seq, routing=None):
            return 2.0 * _first.train_flops_per_token(cfg, seq)
        """))
    cfg = json.load(open(os.path.join(b, "configs",
                                      "kimi-linear-48b-a3b.json")))
    cfg["name"], cfg["family"] = "kimi-twin", "kimi_twin"
    add("configs/kimi-twin.json", json.dumps(cfg))
    lim = json.load(open(os.path.join(b, "limits", CELL + ".json")))
    add("limits/kimi-twin.train_kda_8k.json", json.dumps(lim))

    spec_path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(spec_path))
    spec["configs"].append({
        "name": "kimi-twin", "source": "test",
        "file": "benchmarks/configs/kimi-twin.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append({
        "name": "kimi-twin.train_kda_8k", "config": "kimi-twin",
        "traffic": "train_kda_8k", "chips": 1, "why": "test"})
    json.dump(spec, open(spec_path, "w"))

    rc, out, err = rehearse(root, cell="kimi-twin.train_kda_8k",
                            pythonpath=ROOT)
    assert rc == 0, err[-2000:]
    assert json.loads(out[-1])["correct"] is True
    assert scalars_of(err)["flop_per_token"] == 2.0 * _first_flops()
    for p, data in before.items():
        assert open(p, "rb").read() == data, f"{p} was edited"


def _first_flops():
    from benchmarks.families import kimi_linear

    _, _, cfg, traffic, _ = harness.load_cell(CELL, rehearse=False)
    cfg = harness.merged(cfg, cfg["rehearsal"])
    traffic = harness.merged(traffic, traffic["rehearsal"])
    return kimi_linear.train_flops_per_token(cfg, traffic["seq_len"])


def test_counts_at_the_published_sizes():
    """The cut's arithmetic: the parameter tree's size, and the FLOP a
    token costs at even routing and at the routing a run measured."""
    import jax
    import jax.numpy as jnp
    from benchmarks.families import kimi_linear as family

    _, _, cfg, traffic, _ = harness.load_cell(CELL, rehearse=False)
    tree = jax.eval_shape(lambda: family.make_params(cfg, 1, jnp.bfloat16))
    sizes = [int(np.prod(a.shape)) for a in jax.tree.leaves(tree)]
    assert sum(sizes) == 1_281_911_680
    z = family._sizes(cfg)
    assert z["expert"] == 7_077_888 and z["dense"] == 63_700_992
    assert z["mla"] == 29_114_880 - 512              # less the latent's norm
    seq = traffic["seq_len"]
    even = family.train_flops_per_token(cfg, seq)
    assert abs(even - 2.4473e9) < 1e6
    # half the even share of rows: 4 expert layers x half an expert less
    rows = np.full((3, 4, 32), 256)
    measured = family.train_flops_per_token(
        cfg, seq, {"moe_rows": rows, "tokens_per_step": 16384})
    assert abs((even - measured) - 6 * 4 * 0.5 * z["expert"]) < 1.0
    assert family.flash_calls(cfg, 2, seq) is None
    # the grouped products of one step whose 4 layers took 16,384 rows
    # each: 8 calls a layer, 24 x rows x hidden x width FLOP a layer
    grouped = family.kernel_calls(cfg, np.full((1, 4, 32), 512))
    assert grouped["moe_grouped_dot"]["events"] == "ragged-dot-none"
    calls = grouped["moe_grouped_dot"]["calls"]
    assert len(calls) == 32
    assert sum(c[0] for c in calls) == 4 * 24 * 16384 * 2304 * 1024
    # a call moves its rows in and out and every held expert's matrix
    assert calls[0][1] == 2 * (16384 * (2304 + 2048) + 32 * 2304 * 2048)


# ----------------------------------------------------------------- readers

HLO = """\
HloModule jit_step

%fused_computation.1 (p: f32[8]) -> f32[8] {
  ROOT %multiply.9 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/mpi4torch.kda/mul"}
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %fusion.7 = f32[8]{0} fusion(%gte), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(mpi4torch.kda))/while/body/mul"}
}

ENTRY %main {
  %fusion.1 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(step)/checkpoint/rematted_computation/mpi4torch.moe/dot_general" source_file="x.py"}
  %while.3 = (s32[], f32[8]) while(%t), condition=%cond, body=%body, metadata={op_name="jit(step)/mpi4torch.kda/while"}
  mpi4torch_flash_fwd.2 = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(mpi4torch.mla)/mpi4torch_flash_fwd/pallas_call"}
  %copy.4 = bf16[8]{0} copy(%b)
  %ragged-dot-none.6 = bf16[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %add.5 = f32[] add(%x, %y), metadata={op_name="jit(step)/add"}
}
"""
SCOPES = {"kda": "mpi4torch.kda", "mla": "mpi4torch.mla",
          "moe": "mpi4torch.moe"}


def test_instructions_are_mapped_to_their_scopes():
    table = train_family.op_scopes(HLO, SCOPES, {"ragged-dot": "moe"})
    assert table == {
        "ragged-dot-none.6": ("moe", "ragged-dot"),
        "multiply.9": ("kda", "mul"), "fusion.7": ("kda", "while/body/mul"),
        "fusion.1": ("moe", "dot_general"), "while.3": ("kda", "while"),
        "mpi4torch_flash_fwd.2": ("mla", "mpi4torch_flash_fwd/pallas_call")}


def record_with(events, table, peaks=None):
    ctx = common.Context(root="", cell={}, cfg={}, traffic={}, limits={},
                         peaks=peaks or {}, seed=0, seconds=0.0, trace=True,
                         rehearse=False, t_start=0.0)
    trace = Trace(devices={0: DeviceTrace(sync=events)})
    return common.Record(ctx=ctx, trace=trace, extras={"op_scopes": table})


def test_scope_time_share_counts_each_event_with_its_own_time():
    """A ``while`` of 100 ns holds two body instructions of 30 and 50 ns;
    its own time is 20.  Shares and the time under no scope add up."""
    table = train_family.op_scopes(HLO, SCOPES, {})
    events = [("fusion.1", 0, 40),                   # moe 40
              ("while.3", 50, 150),                  # kda 20 of its own
              ("fusion.7", 60, 90), ("fusion.7", 95, 145),   # kda 30 + 50
              ("mpi4torch_flash_fwd.2", 150, 210),   # mla 60
              ("copy.4", 220, 240)]                  # no scope 20
    rec = record_with(events, table)
    share = {k: scope_time_share.read(rec, {"scope": k}) for k in SCOPES}
    assert share == {"kda": 100 * 100 / 220, "mla": 100 * 60 / 220,
                     "moe": 100 * 40 / 220}
    times, parts = scope_time_share.by_scope(rec.trace, table)
    assert sum(times.values()) == 220 and times[None] == 20
    assert parts["kda", "while/body/mul"] == 80 and parts[None, "copy"] == 20


def test_scope_time_share_finds_nothing_without_a_table_or_a_trace():
    rec = record_with([("fusion.1", 0, 40)], {})
    assert scope_time_share.read(rec, {"scope": "kda"}) is None
    rec = record_with([("fusion.1", 0, 40)], {"fusion.1": ("moe", "dot")})
    assert scope_time_share.read(rec, {"scope": "kda"}) is None
    rec.trace = None
    assert scope_time_share.read(rec, {"scope": "moe"}) is None


def test_routing_readers():
    rows = np.array([[[4, 4], [2, 6]], [[1, 7], [4, 4]]])   # steps, layers, held
    rec = common.Record(extras={"routing": {"moe_rows": rows}})
    # largest over mean: 1, 1.5, 1.75, 1 -> median 1.25
    assert expert_imbalance.read(rec, {"rows": "moe_rows"}) == 1.25
    assert expert_imbalance.read(common.Record(), {"rows": "moe_rows"}) \
        is None


def test_kernel_roofline_reads_the_events_against_the_counted_calls():
    """Two calls, one bound by the products (1 s at the peak), one by the
    bytes (2 s): 3 s at the least over 10 s of events, 9 s of the calls'
    own and 1 s of what prepares them.  Another number of events than
    calls, no trace or no count: nothing."""
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    events = [("ragged-dot-metadata.1", 0, 1_000_000_000),
              ("ragged-dot-none.3", 1_000_000_000, 4_000_000_000),
              ("fusion.1", 4_000_000_000, 5_000_000_000),
              ("ragged-dot-none", 5_000_000_000, 11_000_000_000)]
    args = {"kernel": "moe_grouped_dot"}
    rec = record_with(events, {}, peaks)
    assert kernel_roofline.read(rec, args) is None
    rec.extras["kernel_calls"] = {"moe_grouped_dot": {
        "events": "ragged-dot-none", "beside": "ragged-dot-metadata",
        "calls": [(100.0, 5.0), (50.0, 20.0)]}}
    assert kernel_roofline.read(rec, args) == 30.0
    rec.extras["kernel_calls"]["moe_grouped_dot"]["calls"].append((1.0, 1.0))
    assert kernel_roofline.read(rec, args) is None
    rec.extras["kernel_calls"]["moe_grouped_dot"]["calls"].pop()
    rec.trace = None
    assert kernel_roofline.read(rec, args) is None
