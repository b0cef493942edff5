"""``decode_pages_read_per_live`` and its reader on hand-built step
logs: the ratio over the window's steps, and nothing to read (never an
error) from a program whose step records hold neither count, which is
every commit before the counts existed; the metric's file and entry."""

import json
import os

from benchmarks.readers import step_count_ratio_where_counted as reader
from benchmarks.tests.test_span_readers import (
    ROOT, T_OPEN, a_run, chain, decode_step, record)

ARGS = json.load(open(os.path.join(
    ROOT, "benchmarks", "metrics", "decode_pages_read_per_live.json")))


def counted(rec, live, read):
    return dict(rec, decode_pages_live=live, decode_pages_read=read)


def test_ratio_over_the_windows_steps():
    before, inside, after = a_run()
    # Sixteen slots of 20 pages: the kernel visits what is live, the
    # gather every slot's whole row.
    kernel = [counted(r, 150 + i, 150 + i) for i, r in enumerate(inside)]
    gather = [counted(r, 150 + i, 320) for i, r in enumerate(inside)]
    outside = [counted(r, 1, 1000) for r in before + after]
    assert reader.read(record(outside + kernel), ARGS) == 1.0
    got = reader.read(record(outside + gather), ARGS)
    assert got == 5 * 320 / sum(150 + i for i in range(5))


def test_a_program_without_the_counts_gives_nothing():
    before, inside, after = a_run()
    assert "decode_pages_live" not in inside[0]
    assert reader.read(record(before + inside + after), ARGS) is None
    # one step of the window without them is enough
    mixed = [counted(r, 10, 10) for r in inside[:-1]] + inside[-1:]
    assert reader.read(record(mixed), ARGS) is None
    # as are no log, no step in the window, and no live page
    assert reader.read(record(None), ARGS) is None
    assert reader.read(record([counted(r, 10, 10) for r in before]),
                       ARGS) is None
    idle = chain(T_OPEN + 5_000, [decode_step])
    assert reader.read(record([counted(idle[0], 0, 0)]), ARGS) is None


def test_the_metric_is_the_last_entry():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    m = spec["per_layer"][-1]
    assert m["name"] == "decode_pages_read_per_live"
    assert m["workloads"] == ["internlm2-1.8b.serve_chat"]
    assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
    assert m["layer"] in {p["layer"] for p in spec["per_layer"][:-1]}
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "readers", ARGS["reader"] + ".py"))
