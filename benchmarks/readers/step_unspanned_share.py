"""The share of ``Engine.step()``'s time, over all steps of the
measured window, that lies under none of the step's child spans, %:
the check that the split of a step into phases is whole."""


def read(record, args):
    from benchmarks import program_spans, trace_reduce

    steps = program_spans.steps_of(record)
    if not steps:
        return None
    total = bare = 0.0
    for r in steps:
        whole = [[r["t0_ns"], r["t1_ns"]]]
        children = trace_reduce.union(
            [(t0, t1) for n, t0, t1, _ in r["spans"]
             if n != program_spans.STEP])
        total += r["t1_ns"] - r["t0_ns"]
        bare += trace_reduce.length(trace_reduce.subtract(whole, children))
    return 100.0 * bare / total if total else None
