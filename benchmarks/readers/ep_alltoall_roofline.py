"""Share (%) of the interconnect's peak that the expert layers'
all-to-alls reached in the traced phase: the bytes of rows that leave a
chip a step (padding not counted; the family's ``ep_payload_bytes`` of
the rows the first chip sent, ``extras["routing"][<sent>]``, the mean
over the window's steps: the traffic is the same kind every step) over
``ici_bits_per_s``, against the time of the collectives under ``scope``
a step (``readers/scoped_collective_ms.py``).  Printed to standard error:
the bytes, the time, the least time."""

import sys


def read(record, args):
    import numpy as np

    from benchmarks.readers import scoped_collective_ms
    from benchmarks.traffic_kinds.train_family import load_family

    sent = (record.extras.get("routing") or {}).get(args["sent"])
    steps = scoped_collective_ms.steps(record, args["per"])
    found = scoped_collective_ms.seconds(record, args["scope"])
    if sent is None or not len(sent) or not steps or found is None \
            or not record.ctx.peaks:
        return None
    family = load_family(record.ctx.cfg)
    payload = float(np.mean([family.ep_payload_bytes(record.ctx.cfg, s)
                             for s in np.asarray(sent)]))
    least = payload / (record.ctx.peaks["ici_bits_per_s"] / 8)
    took = found[0] / steps
    print(f"roofline ep_alltoall: {payload:.4g} B of rows leave a chip a "
          f"step, {took:.6f} s in the all-to-alls a step, {least:.6f} s at "
          "the least", file=sys.stderr)
    return 100.0 * least / took
