"""Quantile ``q`` of the samples recorded under ``samples``: the median
as ``statistics.median`` has it, any other by linear interpolation
between order statistics.  Nothing to read where there are no samples."""

import statistics


def read(record, args):
    xs = sorted(record.samples.get(args["samples"]) or [])
    if not xs:
        return None
    q = float(args["q"])
    if q == 0.5:
        return statistics.median(xs)
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
