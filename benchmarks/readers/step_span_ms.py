"""Median, over the measured window's steps of one kind (``steps``:
``admit`` ran a prefill, ``decode`` the rest that decoded, ``all``), of
the milliseconds one step spent in the program's span ``span`` (summed
where a step has several: one per admitted request).  From the
program's step log; nothing to read where the program keeps none."""

import statistics


def read(record, args):
    from benchmarks import program_spans

    steps = program_spans.steps_of(record, args["steps"])
    if not steps:
        return None
    return statistics.median(
        program_spans.span_ns(r, args["span"]) / 1e6 for r in steps)
