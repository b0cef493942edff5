"""Share (%) of the window's sum of one routing counter (``part``) in
the sum of it and another (``rest``), both keys of
``extras["routing"]``: rows of padding over all the buffer rows sent."""


def read(record, args):
    import numpy as np

    routing = record.extras.get("routing") or {}
    part, rest = routing.get(args["part"]), routing.get(args["rest"])
    if part is None or rest is None:
        return None
    whole = float(np.sum(part)) + float(np.sum(rest))
    return 100.0 * float(np.sum(part)) / whole if whole else None
