"""``step_span_ms`` for a span that a program may not have: nothing to
read where no step of that kind in the measured window holds the span
``span`` (every commit before the one that added it); ``step_span_ms``
itself reads 0 there."""


def read(record, args):
    from benchmarks import program_spans
    from benchmarks.readers import step_span_ms

    steps = program_spans.steps_of(record, args["steps"])
    if not steps or not any(n == args["span"] for r in steps
                            for n, *_ in r["spans"]):
        return None
    return step_span_ms.read(record, args)
