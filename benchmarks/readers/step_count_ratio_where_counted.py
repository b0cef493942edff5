"""The sum of the step records' count ``num`` over the sum of their
count ``den``, over the steps of the measured window, for counts that a
program may not keep: nothing to read where any step of the window
lacks either count (every commit before the one that added them), where
the log cannot vouch for the window, or where the denominator is zero.
``step_count_ratio`` reads counts that every step record has had since
the log exists, and raises on a record without them."""


def read(record, args):
    from benchmarks import program_spans

    steps = program_spans.steps_of(record)
    num, den = args["num"], args["den"]
    if not steps or any(num not in r or den not in r for r in steps):
        return None
    total = sum(r[den] for r in steps)
    if not total:
        return None
    return sum(r[num] for r in steps) / total
