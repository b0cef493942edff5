"""1 - (union of the device operations' intervals) / (traced window), %."""


def read(record, args):
    from benchmarks import trace_reduce

    if record.trace is None or not record.trace.devices:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_seconds(record.trace)
                    / trace_reduce.window_seconds(record.trace))
