"""The reduction from a trace to numbers, on a trace recorded on a v5e
(``record_trace.py``: three steps of a tiny program under ``bench.feed``
/ ``bench.train_step`` spans, the flash forward kernel in it) and on
hand-made intervals."""

import os

import pytest

from benchmarks import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "v5e_1chip_small.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return tr.load(DATA)


def test_names():
    assert tr.op_name("%fusion.3 = bf16[2]{0} fusion(%a), kind=kLoop") \
        == "fusion.3"
    assert tr.op_kind("fusion.3") == "fusion"
    assert tr.op_kind("all-reduce-start.1.2") == "all-reduce-start"
    assert tr.is_collective("%psum.3 = bf16[8,128]{1,0:T(8,128)(2,1)} "
                            "all-reduce(bf16[8,128]{1,0} %x), channel_id=1")
    assert tr.is_collective("%ag = (bf16[4]{0}, bf16[16]{0}) "
                            "all-gather-start(bf16[4]{0} %p)")
    assert tr.is_collective("%d.1 = bf16[16]{0} all-gather-done(%ag)")
    assert not tr.is_collective("%fusion.7 = bf16[2]{0} fusion(%all-reduce.1)"
                                ", kind=kLoop, calls=%fused")


def test_interval_arithmetic():
    u = tr.union([(0, 4), (2, 6), (10, 12), (12, 12)])
    assert u == [[0, 6], [10, 12]] and tr.length(u) == 8
    assert tr.subtract([[0, 10]], [[2, 3], [5, 7]]) == [[0, 2], [3, 5], [7, 10]]
    assert tr.subtract([[0, 4], [6, 8]], [[3, 7]]) == [[0, 3], [7, 8]]
    assert tr.clip([[0, 5], [8, 12]], 4, 9) == [[4, 5], [8, 9]]


def test_recorded_trace_planes_and_spans(recorded):
    assert list(recorded.devices) == [0]
    names = [s[0] for s in recorded.host]
    assert names == ["bench.feed", "bench.train_step"] * 3


def test_recorded_busy_and_idle(recorded):
    window = tr.window_seconds(recorded)
    busy = tr.busy_seconds(recorded)
    # three steps of ~50 us of device work inside ~20 ms of 5 ms sleeps
    assert 0.015 < window < 0.03
    assert 1e-4 < busy < 3e-4
    idle = tr.idle_by_span(recorded)
    assert idle["bench.feed"] > 3 * 0.005 * 0.9
    assert abs(sum(idle.values()) + busy - window) < 1e-9 * 10


def test_recorded_kernel_and_breakdown(recorded):
    fwd = tr.kernel_events(recorded, "mpi4torch_flash_fwd")
    assert len(fwd) == 3 and all(3.0e-5 < t < 3.5e-5 for t in fwd)
    assert tr.kernel_events(recorded, "mpi4torch_flash_bwd_dq") == []
    b = tr.breakdown(recorded)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    kinds = dict(b["device_ops"])
    assert abs(kinds["jvp_mpi4torch_flash_fwd_"] - sum(fwd)) < 1e-12
    assert tr.collective_seconds(recorded) == (0.0, 0.0)


def test_collectives_total_and_exposed():
    """One chip: an async all-reduce from 0 to 100 whose done waits from
    60 to 100, a fusion from 10 to 60 hiding the first part, and a
    synchronous reduce-scatter from 120 to 150."""
    dev = tr.DeviceTrace(
        sync=[("all-reduce-start.1", 0, 1), ("fusion.2", 10, 60),
              ("all-reduce-done.1", 60, 100), ("reduce-scatter.3", 120, 150)],
        spans=[("all-reduce-start.1", 0, 100)],
        collectives={"all-reduce-start.1", "all-reduce-done.1",
                     "reduce-scatter.3"})
    trace = tr.Trace(devices={0: dev},
                     host=[("bench.train_step", 0, 200)])
    total, exposed = tr.collective_seconds(trace)
    assert total == pytest.approx(130e-9)
    # hidden only while the fusion runs: 10..60
    assert exposed == pytest.approx(80e-9)
    # the core runs an instruction in 0..1, 10..100 and 120..150
    assert tr.busy_seconds(trace) == pytest.approx(121e-9)
    assert tr.idle_by_span(trace) == {"bench.train_step":
                                      pytest.approx(79e-9)}
    kinds = tr.seconds_by_kind(trace)
    assert kinds["all-reduce-start"] == pytest.approx(1e-9)
    assert kinds["all-reduce-done"] == pytest.approx(40e-9)


def test_idle_goes_to_the_span_that_covers_it():
    dev = tr.DeviceTrace(sync=[("fusion", 0, 10), ("fusion.1", 50, 60)])
    trace = tr.Trace(devices={0: dev}, host=[
        ("bench.a", 0, 20), ("bench.b", 20, 60)])
    assert tr.idle_by_span(trace) == {"bench.b": pytest.approx(40e-9)}


def test_recorded_four_chip_trace_finds_the_all_reduce_by_its_opcode():
    """``record_trace.py`` on four chips: the gradient's ``pmean`` is an
    all-reduce whose instruction is named ``%psum...``."""
    t = tr.load(os.path.join(os.path.dirname(DATA),
                             "v5e_4chip_small.xplane.pb"))
    assert sorted(t.devices) == [0, 1, 2, 3]
    for dev in t.devices.values():
        assert dev.collectives and all(n.startswith("psum")
                                       for n in dev.collectives)
    total, exposed = tr.collective_seconds(t)
    # three steps, one all-reduce of a 1 MB gradient each: tens of us
    assert 3 * 5e-6 < total < 3 * 1e-4 and 0 < exposed <= total
    assert len(tr.kernel_events(t, "mpi4torch_flash_bwd_dq")) == 3
