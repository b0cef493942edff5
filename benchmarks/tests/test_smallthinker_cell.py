"""The cell ``smallthinker-21ba3b.train_ep4_16k`` at its rehearsal sizes,
on four CPU devices with 2 of 8 experts a device: correct as it stands;
not correct with the rows kept at home, with a state the step leaves
alone, or with the float8 control in the program's place; and the
readers this cell brings, on recorded tables."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import common
from benchmarks.families import smallthinker as family
from benchmarks.readers import (ep_alltoall_roofline, routing_share,
                                routing_sum, scoped_collective_ms)
from benchmarks.tests.test_family_kind import ROOT, rehearse
from benchmarks.trace_reduce import DeviceTrace, Trace

CELL = "smallthinker-21ba3b.train_ep4_16k"


def test_the_cell_rehearses_and_is_correct():
    rc, out, err = rehearse(ROOT, cell=CELL)
    assert rc == 0, err[-2000:]
    result = json.loads(out[-1])
    assert result["correct"] is True and result["attempted"] > 0
    assert result["device"]["count"] == 4
    compared = [l for l in out if l.startswith("compared ")]
    assert len(compared) == 4 and all(l.endswith(" ok") for l in compared)
    notes = [l for l in err.splitlines() if l.startswith("notes ")][-1]
    for counter in ("moe_rows", "ep_rows_sent", "ep_rows_received",
                    "ep_padding_rows", "ep_overflow_rounds"):
        assert f'"{counter}"' in notes


@pytest.mark.parametrize("broken,number", [
    ("rows_at_home", "grad_norm_gap"), ("state_unchanged", "param_change_gap")])
def test_a_broken_step_is_not_correct(broken, number):
    """``rows_at_home`` is the break only several chips can show: every
    chip runs the rows it had for the other chips' experts through its
    own (the all-to-all moves nothing); the loss is still the same on
    every rank, and the gradient is another model's."""
    rc, out, err = rehearse(ROOT, "--break", broken, cell=CELL)
    assert rc == 0, err[-2000:]
    assert json.loads(out[-1])["correct"] is False
    assert any(l.startswith(f"compared {number}:") and l.endswith("NOT OK")
               for l in out)
    assert any(l.startswith("compared rank_loss_spread:")
               and l.endswith(" ok") for l in out)


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


# ---------------------------------------------------------------- readers

def _record(**extras):
    ctx = common.Context(
        root="", cell={}, cfg={"family": "smallthinker", "hidden_size": 10,
                               "dtype": "bfloat16"},
        traffic={}, limits={}, peaks={"ici_bits_per_s": 8e9}, seed=0,
        seconds=0.0, trace=True, rehearse=False, t_start=0.0)
    return common.Record(ctx=ctx, extras=extras)


def _traced(record):
    """Two steps; on each of two chips an all-to-all under the exchange's
    scope (one hidden under a fusion, one half exposed), an all-reduce
    under no scope, and a fusion."""
    ms = 1_000_000
    dev = DeviceTrace(
        sync=[("fusion.1", 0, 10 * ms), ("all-to-all.2", 12 * ms, 16 * ms),
              ("all-reduce.3", 20 * ms, 22 * ms)],
        spans=[("all-to-all-start.1", 2 * ms, 6 * ms)],
        collectives={"all-to-all.2", "all-to-all-start.1", "all-reduce.3"})
    record.trace = Trace(devices={0: dev, 1: dev}, host=[
        ("bench.train_step", 0, 11 * ms), ("bench.train_step", 11 * ms,
                                           22 * ms)])
    record.extras["op_scopes"] = {
        "all-to-all.2": ("moe_exchange", "all_to_all"),
        "all-to-all-start.1": ("moe_exchange", "all_to_all"),
        "fusion.1": ("moe", "dot_general")}
    return record


def test_collectives_are_timed_by_scope():
    rec = _traced(_record())
    args = {"scope": "moe_exchange", "per": "bench.train_step"}
    assert scoped_collective_ms.read(rec, dict(args, part="total")) \
        == pytest.approx(4.0)          # (4 + 4) ms over two steps
    assert scoped_collective_ms.read(rec, dict(args, part="exposed")) \
        == pytest.approx(2.0)          # the first hides under the fusion
    assert scoped_collective_ms.read(
        rec, dict(args, scope="attn", part="total")) is None
    assert scoped_collective_ms.read(_record(), dict(args, part="total")) \
        is None                        # no trace: nothing, and no raise


def test_the_all_to_alls_share_of_the_interconnect():
    """Rows sent to the other chips, 6 exchanges a layer, 10 channels of
    2 bytes, against 1 GB/s and 4 ms a step."""
    sent = np.asarray([[[5, 100, 200, 300]] * 2] * 3)    # steps, layers, chips
    rec = _traced(_record(routing={"ep_rows_sent": sent}))
    args = {"scope": "moe_exchange", "per": "bench.train_step",
            "sent": "ep_rows_sent"}
    payload = family.EXCHANGES_A_LAYER * 2 * 600 * 10 * 2
    assert family.ep_payload_bytes(rec.ctx.cfg, sent[0]) == payload
    assert ep_alltoall_roofline.read(rec, args) == pytest.approx(
        100.0 * (payload / 1e9) / 4e-3)
    assert ep_alltoall_roofline.read(_record(), args) is None


def test_counters_of_the_exchange_are_summed_over_the_window():
    rec = _record(routing={"ep_overflow_rounds": np.asarray([[0, 1], [2, 0]]),
                           "ep_padding_rows": np.asarray([[30], [10]]),
                           "ep_rows_sent": np.asarray([[[60, 20]], [[50, 30]]])})
    assert routing_sum.read(rec, {"count": "ep_overflow_rounds"}) == 3.0
    assert routing_share.read(
        rec, {"part": "ep_padding_rows", "rest": "ep_rows_sent"}) == 20.0
    assert routing_sum.read(_record(), {"count": "ep_overflow_rounds"}) is None
    assert routing_share.read(_record(routing={}), {
        "part": "ep_padding_rows", "rest": "ep_rows_sent"}) is None


def test_a_mixed_stacks_kernel_calls_are_costed_by_layer():
    """Per step and layer two forward calls and one backward pair (two
    events), a sliding layer's at its window; eight grouped products."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "smallthinker-21ba3b.json")) as f:
        cfg = json.load(f)
    rows = np.full((2, 8, 16), 6144)
    calls = family.kernel_calls(cfg, rows)
    assert len(calls["moe_grouped_dot"]["calls"]) == 2 * 8 * 8
    assert len(calls["flash_fwd"]["calls"]) == 2 * 8 * 2
    assert len(calls["flash_bwd"]["calls"]) == 2 * 8 * 2
    assert calls["flash_bwd"]["events"] == "mpi4torch_flash_bwd"
    full, sliding = calls["flash_fwd"]["calls"][0], \
        calls["flash_fwd"]["calls"][2]
    pairs = lambda s, w: s * (s + 1) // 2 if not w else \
        w * (w + 1) // 2 + (s - w) * w
    assert full[0] == 28 * 4 * pairs(16384, 0) * 128
    assert sliding[0] == 28 * 4 * pairs(16384, 4096) * 128
    # 6 per matrix parameter a token meets and three times the pairs
    per_token = family.train_flops_per_token(cfg, 16384)
    assert per_token == pytest.approx(
        6 * family.matmul_params_active(cfg)
        + 3 * (2 * full[0] + 6 * sliding[0]) / 16384)
