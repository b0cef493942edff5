"""Each reader on a hand-made record: what it reads, and that a reader
with nothing to read returns nothing."""

import pytest

from benchmarks import common, flops, program, run
from benchmarks import trace_reduce as tr
from benchmarks.readers import (collective_ms, count, device_idle_share,
                                flash_roofline, hbm_peak_share, mfu,
                                quantile, scalar)

PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
CFG = {"num_hidden_layers": 2, "num_attention_heads": 4,
       "num_key_value_heads": 2, "hidden_size": 512}


def record(**kw):
    ctx = common.Context(root="", cell={}, cfg=CFG, traffic={}, limits={},
                         peaks=PEAK, seed=0, seconds=1.0, trace=True,
                         rehearse=False, t_start=0.0)
    return common.Record(ctx=ctx, **kw)


def test_scalar_quantile_count():
    r = record(scalars={"a": 3.5}, samples={"x": [4.0, 1.0, 3.0, 2.0]})
    assert scalar.read(r, {"key": "a"}) == 3.5
    assert scalar.read(r, {"key": "missing"}) is None
    assert quantile.read(r, {"samples": "x", "q": 0.5}) == 2.5
    assert quantile.read(r, {"samples": "x", "q": 0.9}) == pytest.approx(3.7)
    assert quantile.read(r, {"samples": "x", "q": 1.0}) == 4.0
    assert quantile.read(r, {"samples": "none", "q": 0.5}) is None
    assert count.read(r, {"samples": "x"}) == 4
    assert count.read(r, {"samples": "none"}) is None


def test_mfu_and_hbm():
    r = record(scalars={"rate": 1000.0, "flop_per_token": 1.97e9,
                        "live_peak_bytes": 3e9, "program_temp_bytes": 1e9,
                        "bytes_limit": 16e9})
    assert mfu.read(r, {"rate": "rate"}) == pytest.approx(1.0)
    assert hbm_peak_share.read(r, {}) == pytest.approx(25.0)
    assert mfu.read(record(), {"rate": "rate"}) is None
    assert hbm_peak_share.read(record(), {}) is None


def kernel_trace(durations_ns, name):
    t, sync = 0, []
    for d in durations_ns:
        sync.append((f"{name}.{len(sync)}", t, t + d))
        t += d + 1000
    return tr.Trace(devices={0: tr.DeviceTrace(sync=sync)},
                    host=[("bench.train_step", 0, t)])


def test_flash_roofline_uniform_and_in_order():
    fwd = program.kernel_names()["flash_fwd"]
    shape = flops.flash_call_shape(CFG, 1, 1024)
    least, bound = flops.least_seconds(*flops.flash_fwd_cost(shape), PEAK)
    args = {"kernels": ["flash_fwd"], "cost": "flash_fwd_cost"}
    # three calls, each taking four times the least time: 25 %
    r = record(trace=kernel_trace([int(4 * least * 1e9)] * 3, "jvp_" + fwd),
               extras={"flash_calls": {"uniform": shape}})
    assert flash_roofline.read(r, args) == pytest.approx(25.0, rel=1e-3)
    # two admissions (1024 and 256 tokens) x two layers, in order
    small = flops.flash_call_shape(CFG, 1, 256)
    l2, _ = flops.least_seconds(*flops.flash_fwd_cost(small), PEAK)
    ns = [int(2 * least * 1e9)] * 2 + [int(2 * l2 * 1e9)] * 2
    r = record(trace=kernel_trace(ns, fwd),
               extras={"flash_calls": {"in_order": [shape, small]}})
    assert flash_roofline.read(r, args) == pytest.approx(50.0, rel=1e-2)
    # a count that does not fit the admissions: nothing to read
    r.extras["flash_calls"] = {"in_order": [shape]}
    assert flash_roofline.read(r, args) is None
    assert flash_roofline.read(record(), args) is None


def test_collective_and_idle_readers():
    dev = tr.DeviceTrace(
        sync=[("fusion", 0, 4_000_000), ("all-gather-done.1", 4_000_000,
                                         6_000_000)],
        spans=[("all-gather-start.1", 1_000_000, 6_000_000)],
        collectives={"all-gather-start.1", "all-gather-done.1"})
    trace = tr.Trace(devices={0: dev}, host=[
        ("bench.train_step", 0, 5_000_000),
        ("bench.train_step", 5_000_000, 10_000_000)])
    r = record(trace=trace)
    per = {"per": "bench.train_step"}
    assert collective_ms.read(r, {"part": "total", **per}) \
        == pytest.approx(2.5)
    assert collective_ms.read(r, {"part": "exposed", **per}) \
        == pytest.approx(1.0)
    assert device_idle_share.read(r, {}) == pytest.approx(40.0)
    assert collective_ms.read(record(), {"part": "total", **per}) is None
    assert device_idle_share.read(record(), {}) is None


def test_every_metric_of_the_benchmark_has_a_file_and_a_reader():
    spec = run.load_json(run.ROOT, "BENCHMARK.json")
    r = record()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert run.read_metric(m["name"], r) is None     # nothing recorded
    cells = {w["name"] for w in spec["workloads"]}
    ends = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert m["moves"] in ends and set(m["workloads"]) <= cells
