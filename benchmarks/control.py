"""The control of "How correct is decided": the reference put in the
program's place, computed in the nearest precision below the one the
configuration states (float8 e4m3 for bfloat16), has to come out as not
correct.  Run on the chip at a cell's own size, over several seeds:

    python benchmarks/control.py --workload <cell> --seeds 11,12,13

Training cells need no measured window: for each seed the float32
reference and the float8 control follow the first steps from the seed's
weights and batches, and the control's numbers are read against the
reference's exactly as a run reads the program's.  (The program's own
numbers over many seeds come from the benchmark's runs, which print
them.)  A serving cell constructs the engine once and, for each seed,
runs a short window of the cell's own load, then reads the program's
and the control's widest logit gap on the same prompts and tokens.
``--rehearse`` runs the rehearsal sizes on the CPU (the kept test).
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from benchmarks import common, run as harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    _, cell, cfg, traffic, limits = harness.load_cell(args.workload,
                                                      args.rehearse)
    devices = harness.find_devices(cell, args.rehearse)
    if devices is None:
        return 3

    def ctx(seed):
        return common.Context(
            root=ROOT, cell=cell, cfg=cfg, traffic=traffic, limits=limits,
            peaks={}, seed=seed, seconds=args.seconds, trace=False,
            rehearse=args.rehearse, t_start=time.perf_counter(),
            devices=devices)

    driver = importlib.import_module(
        f"benchmarks.traffic_kinds.{traffic['kind']}")
    results = driver.control(ctx, seeds, args.seconds)
    for r in results:
        print("control", json.dumps(r), flush=True)

    # The control has failed where any of its numbers passes its limit.
    failed_all = True
    for r in results:
        over = [n for n, v in r["control"].items()
                if not common.within(v, limits[n])]
        print(f"seed {r['seed']}: control over its limit on {over}")
        failed_all = failed_all and bool(over)
    print(json.dumps({"control_not_correct_on_every_seed": failed_all,
                      "device": devices[0].device_kind}))
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
