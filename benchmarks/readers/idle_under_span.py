"""Of the first chip's idle time in the traced phase, the share that
lies under the program's span ``span``, %.

The program's spans are on ``time.perf_counter_ns()``, the device's
events on the trace's clock.  The traced phase ends before the measured
window opens, and each of its ``Engine.step()`` calls is wrapped by one
of the benchmark's own spans (``bench.engine_step.*``), which the trace
holds.  So the last N step records that ended before the window opened
are, in order, the N such spans: the offset between the clocks is the
median of ``trace start - record start``.  Nothing is read unless the
counts agree and every pair's offset lies within ``TOLERANCE_NS`` of
that median.
"""

import statistics

BENCH_STEP = "bench.engine_step."
TOLERANCE_NS = 1_000_000


def align(record):
    """(the traced phase's step records, offset in ns from the program's
    clock to the trace's), or None where they cannot be laid on each
    other."""
    from benchmarks import program_spans

    w = program_spans.window(record)
    if record.trace is None or w is None:
        return None
    wraps = [s for s in record.trace.host if s[0].startswith(BENCH_STEP)]
    steps = w["before"][-len(wraps):] if wraps else []
    if not wraps or len(steps) != len(wraps):
        return None
    offsets = [s[1] - r["t0_ns"] for s, r in zip(wraps, steps)]
    offset = statistics.median(offsets)
    if any(abs(o - offset) > TOLERANCE_NS for o in offsets):
        return None
    return steps, int(offset)


def read(record, args):
    from benchmarks import trace_reduce as tr

    aligned = align(record)
    if aligned is None or not record.trace.devices:
        return None
    steps, offset = aligned
    lo, hi = tr.window(record.trace)
    dev = record.trace.devices[min(record.trace.devices)]
    idle = tr.subtract([[lo, hi]], tr.clip(tr.busy(dev), lo, hi))
    if not idle:
        return None
    under = tr.union([(t0 + offset, t1 + offset) for r in steps
                      for n, t0, t1, _ in r["spans"] if n == args["span"]])
    return 100.0 * (1.0 - tr.length(tr.subtract(idle, under))
                    / tr.length(idle))
