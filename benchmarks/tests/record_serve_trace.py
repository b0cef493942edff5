"""Record the small serving trace, with its step log, that
test_idle_owners.py reads.

Run on the chip (``python benchmarks/tests/record_serve_trace.py``):
the ``internlm2-1.8b.serve_chat`` cell through its own traffic kind,
cut to four layers, a quarter of a second of traced phase and one
second of window, of which the first forty steps are kept.  Writes
``chiprun_out/trace_small/serve_1chip.json``: the trace as ``trace_reduce`` holds it (the first chip's events, the
harness's host spans), the step records of the traced phase and of the
window's beginning, what ``program_spans.window`` needs of the run's
scalars, and every per-layer metric the readers found in just that,
for the test to find again.
"""

from __future__ import annotations

import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CELL = "internlm2-1.8b.serve_chat"
KEEP_WINDOW_STEPS = 40


def pack(spec: dict, rec) -> dict:
    """What the test needs of a run's record, as JSON holds it: the
    traced phase's steps and the window's first ``KEEP_WINDOW_STEPS``
    (the window's length cut to them), times of the trace counted from
    its first host span."""
    from benchmarks import program_spans, run

    t_open = program_spans.window(rec)["t_open_ns"]
    records = rec.extras["step_log"]["records"]
    window = [r for r in records if r["t0_ns"] >= t_open]
    if len(window) > KEEP_WINDOW_STEPS:
        rec.scalars["window_s"] = (
            window[KEEP_WINDOW_STEPS]["t0_ns"] - t_open) / 1e9
        window = window[:KEEP_WINDOW_STEPS]
    wraps = [s for s in rec.trace.host
             if s[0].startswith("bench.engine_step.")]
    before = [r for r in records if r["t1_ns"] <= t_open][-len(wraps):]
    rec.extras["step_log"]["records"] = before + window
    metrics = {}
    for m in spec["per_layer"]:
        if run.applies(m, CELL):
            value = run.read_metric(m["name"], rec)
            if value is not None:
                metrics[m["name"]] = float(value)
    t_first = min(s[1] for s in rec.trace.host)
    dev = rec.trace.devices[min(rec.trace.devices)]
    return {
        "t_start": rec.ctx.t_start,
        "scalars": {k: rec.scalars[k] for k in ("setup_s", "window_s")},
        "step_log": rec.extras["step_log"],
        "host": [[n, a - t_first, b - t_first] for n, a, b in rec.trace.host],
        "sync": [[n, a - t_first, b - t_first] for n, a, b in dev.sync],
        "metrics": metrics}


def main() -> int:
    from benchmarks import common, run

    spec, cell, cfg, traffic, limits = run.load_cell(CELL, rehearse=False)
    devices = run.find_devices(cell, rehearse=False)
    if devices is None:
        return 3
    cfg = dict(cfg, num_hidden_layers=4)
    traffic = dict(traffic, trace_seconds=0.25)      # ~80 steps
    peaks = run.load_json(run.HERE, "peaks.json")[devices[0].device_kind]
    ctx = common.Context(
        root=ROOT, cell=cell, cfg=cfg, traffic=traffic, limits=limits,
        peaks=peaks, seed=36, seconds=1.0, trace=True, rehearse=False,
        t_start=run.T_START, devices=devices)
    driver = importlib.import_module(
        f"benchmarks.traffic_kinds.{traffic['kind']}")
    rec = driver.run(ctx)
    print("correct", rec.correct, rec.compared, file=sys.stderr)

    out = pack(spec, rec)
    path = os.path.join(ROOT, "chiprun_out", "trace_small")
    os.makedirs(path, exist_ok=True)
    out["device_kind"] = devices[0].device_kind
    with open(os.path.join(path, "serve_1chip.json"), "w") as f:
        json.dump(out, f, separators=(",", ":"))
    print(json.dumps({"steps": len(out["step_log"]["records"]),
                      "events": len(out["sync"]),
                      "metrics": out["metrics"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
