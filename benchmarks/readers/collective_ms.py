"""Device milliseconds a step in collectives (all-reduce, reduce-scatter,
all-gather, collective-permute, all-to-all), from the traced phase,
averaged over the chips: ``part`` = ``total`` or ``exposed`` (no other
instruction runs on that chip meanwhile).  A step is one host span named
``per``."""


def read(record, args):
    from benchmarks import trace_reduce

    if record.trace is None:
        return None
    steps = sum(1 for name, _, _ in record.trace.host if name == args["per"])
    if not steps:
        return None
    total, exposed = trace_reduce.collective_seconds(record.trace)
    return 1e3 * {"total": total, "exposed": exposed}[args["part"]] / steps
