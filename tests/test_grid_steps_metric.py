"""``decode_grid_steps`` (ISSUE 40) from the engine's counter to the
benchmark's metric: the metric's file and entry, its reader on
hand-built step logs (a ratio where every step of the window holds both
counts, nothing to read and never an error on a program from before the
counter), and what it reads at the three serving cells' shapes."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmarks.readers import step_count_ratio_where_counted as reader
from benchmarks.tests.test_span_readers import ROOT, a_run, record
from mpi4torch_tpu.ops import paged_attention as pa

NAME = "decode_grid_steps_per_live_page"
ARGS = json.load(open(os.path.join(ROOT, "benchmarks", "metrics",
                                   NAME + ".json")))
CELLS = ["internlm2-1.8b.serve_chat",
         "openpangu-ultra-moe-718b.serve_latent_4k",
         "longcat-flash-chat.serve_scmoe_1k"]


def counted(rec, live, steps):
    return dict(rec, decode_pages_live=live, decode_grid_steps=steps)


def test_the_metric_names_the_counter_and_lists_its_cells():
    """PR 40's entry, as it was appended; later cells whose attention
    reads through the paged kernel are appended to its list (PR 41)."""
    assert ARGS == {"reader": "step_count_ratio_where_counted",
                    "num": "decode_grid_steps", "den": "decode_pages_live"}
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (m,) = [p for p in spec["per_layer"] if p["name"] == NAME]
    assert dict(m, workloads=m["workloads"][:len(CELLS)]) == {
        "name": NAME, "unit": "ratio", "better": "lower",
        "source": "program_counter",
        "layer": "kernels (ops/flash.py)", "moves": "serve_tok_s",
        "workloads": CELLS}
    assert m["layer"] in {p["layer"] for p in spec["per_layer"]
                          if p is not m}
    assert set(CELLS) <= {w["name"] for w in spec["workloads"]}
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "readers", ARGS["reader"] + ".py"))


def test_the_engine_counts_what_the_metric_reads():
    from mpi4torch_tpu.serve.__main__ import MIRRORED_SERVE_COUNTERS
    from mpi4torch_tpu.utils import profiling
    assert ARGS["num"] in profiling.ServeStats._COUNTERS
    assert ARGS["num"] in MIRRORED_SERVE_COUNTERS
    assert (ARGS["num"], ARGS["num"]) in profiling._STEP_COUNTS


def test_ratio_over_the_windows_steps():
    before, inside, after = a_run()
    outside = [counted(r, 1, 1000) for r in before + after]
    steps = [counted(r, 790 + i, 256) for i, r in enumerate(inside)]
    got = reader.read(record(outside + steps), ARGS)
    assert got == len(inside) * 256 / sum(790 + i
                                          for i in range(len(inside)))
    # through the gather no kernel walks a grid
    gather = [counted(r, 790, 0) for r in inside]
    assert reader.read(record(gather), ARGS) == 0.0


def test_a_program_from_before_the_counter_gives_nothing():
    before, inside, after = a_run()
    parent = [dict(r, decode_pages_live=10, decode_pages_read=10)
              for r in before + inside + after]
    assert reader.read(record(parent), ARGS) is None
    mixed = [counted(r, 10, 4) for r in inside[:-1]] \
        + [dict(inside[-1], decode_pages_live=10)]
    assert reader.read(record(mixed), ARGS) is None
    assert reader.read(record(None), ARGS) is None


@pytest.mark.parametrize("cell,slots,n_blk,pools,live,one_page,reads", [
    # live pages a decode step: PERF.md section 5 (the cells' traces)
    (CELLS[0], 16, 20, [(320, 128, 8, 128)] * 2, 160, 2.0, 0.5),
    (CELLS[1], 32, 64, [(2048, 128, 1, 640)], 790, 2.6, 0.32),
    (CELLS[2], 32, 32, [(1024, 128, 1, 640)], 443, 2.3, 0.29),
])
def test_what_it_reads_at_the_cells_shapes(cell, slots, n_blk, pools, live,
                                           one_page, reads):
    grid = pa.read_grid(slots, n_blk, *[
        jax.ShapeDtypeStruct(p, jnp.bfloat16) for p in pools])
    assert round(slots * n_blk / live, 1) == one_page
    assert round(grid[0] * grid[1] / live, 2) == reads
    assert grid[0] * grid[1] / live < 0.6
