"""The program's set-up log, as the benchmark reads it.

Beside ``program_spans.py``: ``mpi4torch_tpu.utils.profiling``'s
``compile_log()`` (one record per trace, lowering and backend
compilation JAX made in the process, with the program's name, the
persistent cache's answer and the span it ran under) and
``setup_spans()`` (the spans that closed while no ``Engine.step()`` was
open: the constructor's phases), both on ``time.perf_counter_ns()``.  A
program that keeps neither (every commit before PR 50) gives ``None``
here, and every reader that goes through this file then finds nothing
to read.

The logs are read once per run, when the first metric asks, and kept
in the record (``extras["setup_log"]``).  They are cut by what the
record already holds, as the step log is: set-up is everything that
ended by ``ctx.t_start + scalars["setup_s"]``, the window the
``scalars["window_s"]`` behind it.  Both logs are rings: one that is
full may have dropped set-up's records, and then nothing is read.
"""

from __future__ import annotations

PROGRAM_TEXTS = "mpi4torch.serve.program_texts"


def setup_log():
    """``{"compiles": [...], "compile_cap": n, "spans": [(name, t0_ns,
    t1_ns, rid, engine), ...], "span_cap": n}`` from the program, oldest
    first, or None where the program keeps no compile log."""
    try:
        from mpi4torch_tpu.utils import profiling
    except ImportError:
        return None
    read = getattr(profiling, "compile_log", None)
    if read is None:
        return None
    return {"compiles": read(), "compile_cap": int(profiling.COMPILE_LOG_CAP),
            "spans": profiling.setup_spans(),
            "span_cap": int(profiling.SETUP_SPAN_CAP)}


def log_of(record):
    """The run's set-up log, read from the program once and then kept in
    the record."""
    if "setup_log" not in record.extras:
        record.extras["setup_log"] = setup_log()
    return record.extras["setup_log"]


def cut(record):
    """The logs against set-up and the window: ``{"setup": compile
    records that ended in set-up, "window": those that ended inside the
    window, "spans": set-up spans that closed in set-up, "t_start_ns",
    "t_open_ns"}``.  What ``Engine.program_texts()`` lowered and compiled
    to read the programs' texts is in neither list.  None where there is
    no log or no window, or where a ring is full."""
    log = log_of(record)
    s = record.scalars
    if not log or "setup_s" not in s or "window_s" not in s:
        return None
    if len(log["compiles"]) >= log["compile_cap"] \
            or len(log["spans"]) >= log["span_cap"]:
        return None
    t_open = int(round((record.ctx.t_start + s["setup_s"]) * 1e9))
    t_end = t_open + int(round(s["window_s"] * 1e9))
    own = [r for r in log["compiles"] if r["span"] != PROGRAM_TEXTS]
    return {
        "setup": [r for r in own if r["t1_ns"] <= t_open],
        "window": [r for r in own if t_open < r["t1_ns"] <= t_end],
        "spans": [sp for sp in log["spans"] if sp[2] <= t_open],
        "t_start_ns": int(round(record.ctx.t_start * 1e9)),
        "t_open_ns": t_open}


def chosen(records: list, args: dict) -> list:
    """The compile records a metric's file names: of ``kinds`` (all
    where absent), with the persistent cache's answer among ``cache``
    (any where absent), under the span ``under`` or a child of it."""
    kinds, cache, under = (args.get(k) for k in ("kinds", "cache", "under"))
    return [r for r in records
            if (kinds is None or r["kind"] in kinds)
            and (cache is None or r.get("cache") in cache)
            and (under is None or (r["span"] or "").startswith(under))]
