"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  Finds the cell in ``BENCHMARK.json`` and everything else by
name: the configuration's file, ``traffic/<mix>.json``, the driver of
the mix's kind in ``traffic_kinds/<kind>.py``, ``limits/<cell>.json``,
each metric's ``metrics/<metric>.json`` and the reader it names in
``readers/<reader>.py``.  Fails off the TPU (exit 3, no result line)
unless ``--rehearse`` is given, which runs the configuration's and the
mix's ``rehearsal`` sizes on the CPU, labels the device ``cpu`` and
prints no metric.  The last line of standard output is the result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r}")


def merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def load_cell(workload: str, rehearse: bool) -> tuple:
    """(spec, cell, configuration, traffic mix, limits) of a workload, at
    the rehearsal sizes where asked; a rehearsal also pins JAX to as many
    CPU devices as the cell has chips."""
    spec = load_json(ROOT, "BENCHMARK.json")
    cell = by_name(spec["workloads"], workload, "workload")
    cfg = load_json(ROOT, by_name(spec["configs"], cell["config"],
                                  "configuration")["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    limits = load_json(HERE, "limits", cell["name"] + ".json")
    if rehearse:
        cfg = merged(cfg, cfg["rehearsal"])
        traffic = merged(traffic, traffic["rehearsal"])
        limits = merged(limits, limits["rehearsal"])
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
    return spec, cell, cfg, traffic, limits["limits"]


def find_devices(cell: dict, rehearse: bool):
    """The cell's chips, or None (with the reason on standard error)
    where JAX finds no TPU or too few.  Off a rehearsal this also turns
    the persistent compile cache on, before the first compilation."""
    from benchmarks import program
    if not rehearse:
        program.use_compile_cache()
    import jax

    devices = jax.devices()
    if (devices[0].platform != "tpu" and not rehearse) \
            or len(devices) < cell["chips"]:
        print(f"benchmark: {cell['name']} needs {cell['chips']} TPU chip(s);"
              f" JAX found {len(devices)} x {devices[0].platform}",
              file=sys.stderr)
        return None
    return devices[:cell["chips"]]


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def read_metric(name: str, record):
    """The metric's own file names its reader; a reader that finds
    nothing to read returns None."""
    args = load_json(HERE, "metrics", name + ".json")
    reader = importlib.import_module(f"benchmarks.readers.{args['reader']}")
    return reader.read(record, args)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, rehearsal sizes, no device metric")
    ap.add_argument("--break", dest="broken", default="",
                    help=argparse.SUPPRESS)   # the benchmark's tests only
    args = ap.parse_args(argv)

    if args.broken and not args.rehearse:
        raise SystemExit("benchmark: --break is for rehearsals only")
    spec, cell, cfg, traffic, limits = load_cell(args.workload, args.rehearse)
    devices = find_devices(cell, args.rehearse)
    if devices is None:
        return 3
    platform = devices[0].platform
    kind = devices[0].device_kind
    peaks = {}
    if not args.rehearse:
        table = load_json(HERE, "peaks.json")
        if kind not in table:
            print(f"benchmark: no peaks for device kind {kind!r}",
                  file=sys.stderr)
            return 3
        peaks = table[kind]

    from benchmarks import common
    ctx = common.Context(
        root=ROOT, cell=cell, cfg=cfg, traffic=traffic, limits=limits,
        peaks=peaks, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), rehearse=args.rehearse, t_start=T_START,
        devices=devices, broken=args.broken)
    driver = importlib.import_module(
        f"benchmarks.traffic_kinds.{traffic['kind']}")
    record = driver.run(ctx)

    print("notes", json.dumps(record.extras.get("notes", {})),
          json.dumps({k: v for k, v in record.scalars.items()}),
          file=sys.stderr, flush=True)
    for name, value, limit in record.compared:
        print(f"compared {name}: {value!r} (limit {limit!r}) "
              f"{'ok' if common.within(value, limit) else 'NOT OK'}",
              flush=True)

    metrics = {}
    if not args.rehearse:
        group = spec["per_layer"] if args.trace else spec["end_to_end"]
        for m in group:
            if not applies(m, cell["name"]):
                continue
            value = read_metric(m["name"], record)
            if value is None:
                if not args.trace:
                    raise SystemExit(
                        f"benchmark: end-to-end metric {m['name']} has no "
                        f"value in {cell['name']}")
                continue
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(record.memory_peak_bytes)}
    result = {"correct": record.correct, "attempted": record.attempted,
              "failed": record.failed, "metrics": metrics, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    if record.trace is not None and not args.rehearse:
        from benchmarks import trace_reduce
        device["busy_s"] = trace_reduce.busy_seconds(record.trace)
        device["window_s"] = trace_reduce.window_seconds(record.trace)
        result["breakdown"] = trace_reduce.breakdown(record.trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
