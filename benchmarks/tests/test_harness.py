"""The command's contract off the chip, and discovery: a configuration,
a traffic mix, a per-layer metric and a cell added as new files plus an
entry are found with no edit to a file that was there."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "mistral-7b-v0.1.train_1chip"


def bench(root, *args, pythonpath=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONPATH")}
    env["JAX_PLATFORMS"] = "cpu"
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    p = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    return p.returncode, p.stdout.strip().splitlines(), p.stderr


def copy_benchmark(tmp_path):
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def test_off_the_tpu_the_command_fails_and_prints_no_result():
    rc, out, err = bench(ROOT, "--workload", CELL, "--seed", "1",
                         "--seconds", "1", "--trace", "0")
    assert rc != 0 and out == [] and "TPU" in err


def test_rehearsal_labels_the_device_cpu_and_prints_no_metric():
    rc, out, err = bench(ROOT, "--workload", CELL, "--seed", "1",
                         "--seconds", "1", "--trace", "1", "--rehearse")
    assert rc == 0, err[-2000:]
    result = json.loads(out[-1])
    assert result["device"]["platform"] == "cpu" and result["rehearsal"]
    assert result["metrics"] == {} and "breakdown" not in result
    assert "busy_s" not in result["device"]


def test_without_the_program_the_command_fails(tmp_path):
    root = copy_benchmark(tmp_path)
    rc, out, _ = bench(root, "--workload", CELL, "--seed", "1",
                       "--seconds", "1", "--trace", "0", "--rehearse")
    assert rc != 0 and out == []


def test_unknown_cell_fails():
    rc, out, _ = bench(ROOT, "--workload", "no.such_cell", "--seed", "1",
                       "--seconds", "1", "--trace", "0", "--rehearse")
    assert rc != 0 and out == []


def test_new_files_and_an_entry_are_found(tmp_path):
    root = copy_benchmark(tmp_path)
    b = os.path.join(root, "benchmarks")
    before = {}
    for d, _, files in os.walk(b):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()

    def add(rel, data):
        p = os.path.join(b, rel)
        assert not os.path.exists(p)
        with open(p, "w") as f:
            json.dump(data, f)

    cfg = json.load(open(os.path.join(b, "configs", "internlm2-1.8b.json")))
    cfg["name"] = "internlm2-tiny"
    cfg["rehearsal"]["num_hidden_layers"] = 3
    add("configs/internlm2-tiny.json", cfg)
    mix = json.load(open(os.path.join(b, "traffic", "train_1chip.json")))
    mix["rehearsal"] = {"seq_len": 32, "sequences_per_chip": 3}
    add("traffic/train_short.json", mix)
    lim = json.load(open(os.path.join(b, "limits", CELL + ".json")))
    add("limits/internlm2-tiny.train_short.json", lim)
    add("metrics/train_step_p90_ms.json",
        {"reader": "quantile", "samples": "train_step_ms", "q": 0.9})

    spec_path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(spec_path))
    spec["configs"].append({
        "name": "internlm2-tiny", "source": "test",
        "file": "benchmarks/configs/internlm2-tiny.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append({
        "name": "internlm2-tiny.train_short", "config": "internlm2-tiny",
        "traffic": "train_short", "chips": 1, "why": "test"})
    spec["per_layer"].append({
        "name": "train_step_p90_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "model (models/transformer.py)",
        "moves": "train_tok_s_chip",
        "workloads": ["internlm2-tiny.train_short"]})
    json.dump(spec, open(spec_path, "w"))

    rc, out, err = bench(root, "--workload", "internlm2-tiny.train_short",
                         "--seed", "5", "--seconds", "1", "--trace", "0",
                         "--rehearse", pythonpath=ROOT)
    assert rc == 0, err[-2000:]
    assert json.loads(out[-1])["correct"] is True
    # 3 sequences of 32 tokens a step, through 3 layers
    notes = [l for l in err.splitlines() if l.startswith("notes ")][-1]
    assert '"tokens_per_step": 96' in notes

    # the new metric's reader is found by the metric's own file
    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmarks import run, common; "
            "r = common.Record(samples={'train_step_ms': [1.0, 2.0, 11.0]}); "
            "print(run.read_metric('train_step_p90_ms', r))" % root)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=root)
    assert p.returncode == 0, p.stderr
    assert abs(float(p.stdout) - 9.2) < 1e-9

    for p, data in before.items():
        assert open(p, "rb").read() == data, f"{p} was edited"
