"""``flash_fwd_calls_per_layer.train`` and its reader: on the trace
recorded on a v5e (``record_trace.py``: three steps, one flash forward a
step), on hand-made events under both of the forward's names, and
nothing to read (never an error) without a trace, a step count or an
event; the metric's file and entry."""

import json
import os

from benchmarks import common, program, run
from benchmarks import trace_reduce as tr
from benchmarks.readers import kernel_calls_per_layer as reader
from benchmarks.tests.test_readers import kernel_trace
from benchmarks.tests.test_trace_reduce import DATA

NAME = "flash_fwd_calls_per_layer.train"
ARGS = json.load(open(os.path.join(run.HERE, "metrics", NAME + ".json")))


def record(trace, layers=1, steps=3):
    ctx = common.Context(
        root="", cell={}, cfg={"num_hidden_layers": layers},
        traffic={"trace_steps": steps}, limits={}, peaks={}, seed=0,
        seconds=1.0, trace=True, rehearse=False, t_start=0.0)
    return common.Record(ctx=ctx, trace=trace)


def test_the_recorded_trace_runs_the_forward_once_a_step():
    assert reader.read(record(tr.load(DATA)), ARGS) == 1.0
    # the same three events held against two layers a step
    assert reader.read(record(tr.load(DATA), layers=2), ARGS) == 0.5


def test_a_recomputed_forward_is_a_call_under_either_name():
    fwd = program.kernel_names()["flash_fwd"]
    # four steps of four layers: the forward pass's call and the
    # backward pass's second run of it (the name a jvp gives it)
    once = kernel_trace([3000] * 16, fwd)
    again = kernel_trace([3000] * 16, "jvp_" + fwd + "_")
    both = tr.Trace(devices={0: tr.DeviceTrace(
        sync=once.devices[0].sync + again.devices[0].sync)}, host=[])
    assert reader.read(record(once, layers=4, steps=4), ARGS) == 1.0
    assert reader.read(record(both, layers=4, steps=4), ARGS) == 2.0
    # two of the four layers keep their pair
    some = tr.Trace(devices={0: tr.DeviceTrace(
        sync=once.devices[0].sync + again.devices[0].sync[:8])}, host=[])
    assert reader.read(record(some, layers=4, steps=4), ARGS) == 1.5


def test_nothing_to_read_gives_nothing():
    assert reader.read(record(None), ARGS) is None
    assert reader.read(record(tr.load(DATA), steps=0), ARGS) is None
    other = kernel_trace([3000] * 4, "fusion")
    assert reader.read(record(other), ARGS) is None
    assert reader.read(record(tr.Trace(devices={}, host=[])), ARGS) is None


def test_the_metric_lists_the_two_mistral_cells():
    spec = run.load_json(run.ROOT, "BENCHMARK.json")
    (m,) = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert m["workloads"] == ["mistral-7b-v0.1.train_1chip",
                              "mistral-7b-v0.1.train_dp4"]
    assert m["moves"] == "train_tok_s_chip" and m["better"] == "lower"
