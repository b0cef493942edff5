"""Largest over mean rows of a held expert, the median over the window's
steps and expert layers.  ``extras["routing"][<rows>]`` holds the rows
each held expert took, ``(steps, layers, held)``; printed beside it on
standard error: the counters' totals."""

import sys


def read(record, args):
    import numpy as np

    rows = (record.extras.get("routing") or {}).get(args["rows"])
    if rows is None or not rows.size:
        return None
    rows = np.asarray(rows, np.float64)
    mean = rows.mean(axis=-1)
    if not (mean > 0).all():
        return None
    ratio = rows.max(axis=-1) / mean
    print(f"expert imbalance: {rows.shape[0]} steps x {rows.shape[1]} "
          f"layers x {rows.shape[2]} held; rows an expert min "
          f"{int(rows.min())} mean {rows.mean():.1f} max {int(rows.max())}",
          file=sys.stderr)
    return float(np.median(ratio))
