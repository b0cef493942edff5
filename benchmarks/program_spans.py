"""The program's step log, as the benchmark reads it.

Beside ``program.py`` the one other file of the benchmark that calls
into the program: ``mpi4torch_tpu.utils.profiling.serve_step_log()``,
the process-wide record of every ``Engine.step()`` call with its spans
(``mpi4torch.serve.step.*``) on ``time.perf_counter_ns()``.  A program
that keeps no such log (every commit before PR 25) gives ``None`` here,
and every reader that goes through this file then finds nothing to
read.

The log is read once per run and kept in the record
(``extras["step_log"]``), so that readers take their numbers from the
record like every other reader.  It is cut to the measured window by
what the record already holds, all on ``time.perf_counter()``: the
window opens at ``ctx.t_start + scalars["setup_s"]`` and lasts
``scalars["window_s"]``.
"""

from __future__ import annotations

STEP = "mpi4torch.serve.step"


def step_log():
    """``{"records": [...], "cap": n}`` from the program, oldest first,
    or None where the program keeps no step log."""
    try:
        from mpi4torch_tpu.utils import profiling
    except ImportError:
        return None
    read = getattr(profiling, "serve_step_log", None)
    if read is None:
        return None
    return {"records": read(), "cap": int(profiling.STEP_LOG_CAP)}


def log_of(record):
    """The run's step log, read from the program once and then kept in
    the record."""
    if "step_log" not in record.extras:
        record.extras["step_log"] = step_log()
    return record.extras["step_log"]


def window(record):
    """The step log against the measured window: ``{"steps": records
    that began inside the window, "before": records that ended before it
    opened, "t_open_ns", "dropped": whether the ring may already have
    dropped the window's first steps}``, or None where there is no log
    or no window."""
    log = log_of(record)
    s = record.scalars
    if not log or "setup_s" not in s or "window_s" not in s:
        return None
    t_open = int(round((record.ctx.t_start + s["setup_s"]) * 1e9))
    t_end = t_open + int(round(s["window_s"] * 1e9))
    recs = log["records"]
    return {
        "steps": [r for r in recs if t_open <= r["t0_ns"] < t_end],
        "before": [r for r in recs if r["t1_ns"] <= t_open],
        "t_open_ns": t_open,
        "dropped": bool(recs) and len(recs) >= log["cap"]
        and recs[0]["t0_ns"] >= t_open}


def steps_of(record, kind: str = "all"):
    """The window's steps of one kind: ``admit`` ran a prefill
    (``prefill_tokens > 0``), ``decode`` is the rest that decoded
    (``active > 0``), ``all`` is every step.  None where the log cannot
    vouch for the whole window."""
    w = window(record)
    if w is None or w["dropped"]:
        return None
    if kind == "all":
        return w["steps"]
    if kind == "admit":
        return [r for r in w["steps"] if r["prefill_tokens"] > 0]
    if kind == "decode":
        return [r for r in w["steps"]
                if r["prefill_tokens"] == 0 and r["active"] > 0]
    raise ValueError(f"unknown kind of step {kind!r}")


def span_ns(rec: dict, name: str) -> int:
    """Summed duration of the spans of that name in one step."""
    return sum(t1 - t0 for n, t0, t1, _ in rec["spans"] if n == name)
