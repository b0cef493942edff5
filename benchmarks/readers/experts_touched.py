"""Held experts that took at least one row, the median over the
window's calls of one program and its expert layers: what sets the
expert bytes a decode step must read.  ``extras["routing"][<rows>]``
holds the rows each held expert took, ``(calls, layers, held)``, for the
program ``rows`` names (``decode`` | ``prefill``); printed beside it on
standard error: the rows a held expert took."""

import sys


def read(record, args):
    import numpy as np

    rows = (record.extras.get("routing") or {}).get(args["rows"])
    if rows is None or not rows.size:
        return None
    rows = np.asarray(rows)
    touched = (rows > 0).sum(axis=-1)
    print(f"experts touched ({args['rows']}): {rows.shape[0]} calls x "
          f"{rows.shape[1]} layers x {rows.shape[2]} held; touched min "
          f"{int(touched.min())} mean {touched.mean():.2f} max "
          f"{int(touched.max())}; rows a held expert mean "
          f"{rows.mean():.2f} max {int(rows.max())}", file=sys.stderr)
    return float(np.median(touched))
