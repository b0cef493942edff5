"""The sum of one of the step's routing counters (``count``: a key of
``extras["routing"]``, stacked over the window's steps) over the window.
Nothing to read where the program hands out no such counter."""


def read(record, args):
    import numpy as np

    count = (record.extras.get("routing") or {}).get(args["count"])
    if count is None or not np.size(count):
        return None
    return float(np.sum(count))
