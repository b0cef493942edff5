"""``correct`` has been shown to fail: the harness, with its look for a
chip skipped (``--rehearse``: the rehearsal sizes on the CPU), drives a
whole run; sound it comes out correct, with the timed path broken
underneath it comes out not correct, and the float8 control of
``control.py`` passes a limit on every seed."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAIN, DP4, SERVE = ("mistral-7b-v0.1.train_1chip",
                     "mistral-7b-v0.1.train_dp4",
                     "internlm2-1.8b.serve_chat")


def bench(*args, script="run.py"):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", script), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    return p.returncode, p.stdout.strip().splitlines(), p.stderr


def run_cell(cell, *more):
    rc, out, err = bench("--workload", cell, "--seed", "2147483659",
                         "--seconds", "1", "--trace", "0", "--rehearse",
                         *more)
    assert rc == 0, err[-2000:]
    return json.loads(out[-1]), out


@pytest.mark.parametrize("cell", [TRAIN, DP4, SERVE])
def test_sound_run_is_correct_and_prints_each_number_beside_its_limit(cell):
    result, out = run_cell(cell)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    compared = [l for l in out if l.startswith("compared ")]
    assert compared and all("(limit " in l and l.endswith(" ok")
                            for l in compared)


@pytest.mark.parametrize("cell,broken,number", [
    (TRAIN, "state_unchanged", "param_change_gap"),
    (TRAIN, "half_batch", "grad_norm_gap"),
    (DP4, "no_exchange", "rank_loss_spread"),
    (SERVE, "wrong_token", "served_logit_gap"),
])
def test_broken_timed_path_is_not_correct(cell, broken, number):
    result, out = run_cell(cell, "--break", broken)
    assert result["correct"] is False
    assert any(l.startswith(f"compared {number}:") and l.endswith("NOT OK")
               for l in out)


def test_break_is_refused_outside_a_rehearsal():
    rc, out, _ = bench("--workload", TRAIN, "--seed", "1", "--seconds", "1",
                       "--trace", "0", "--break", "state_unchanged")
    assert rc != 0 and not out


@pytest.mark.parametrize("cell,seconds", [(TRAIN, "0"), (SERVE, "1")])
def test_float8_control_is_not_correct_on_any_seed(cell, seconds):
    rc, out, err = bench("--workload", cell, "--seeds", "11,12,13",
                         "--seconds", seconds, "--rehearse",
                         script="control.py")
    assert rc == 0, err[-2000:]
    assert json.loads(out[-1])["control_not_correct_on_every_seed"] is True
    assert sum(l.startswith("control ") for l in out) == 3
