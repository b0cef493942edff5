"""Median, over the measured window, of the milliseconds between the
end of one ``Engine.step()`` call and the start of the next of the same
engine (``t0_ns[k + 1] - t1_ns[k]`` of the step log): the caller's
time, here the harness's bookkeeping between two steps, in a
deployment the front end's.  Nothing to read where the program keeps no
step log, where the log cannot vouch for the window, or where the
records lack the count ``SINCE`` (a program older than the metric)."""

import statistics

# On every step record since the commit that brought this metric.
SINCE = "decode_uploads"


def read(record, args):
    from benchmarks import program_spans

    steps = program_spans.steps_of(record)
    if not steps or any(SINCE not in r for r in steps):
        return None
    last, gaps = {}, []
    for r in steps:
        if r["engine"] in last:
            gaps.append((r["t0_ns"] - last[r["engine"]]) / 1e6)
        last[r["engine"]] = r["t1_ns"]
    return statistics.median(gaps) if gaps else None
