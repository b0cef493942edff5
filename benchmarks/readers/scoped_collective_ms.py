"""Device milliseconds a step in the collectives that run under one scope
of the program (``scope``: a key of the family's ``scopes()``), from the
traced phase, averaged over the chips: ``part`` = ``total`` or
``exposed`` (no instruction that is not such a collective runs on that
chip meanwhile).  ``readers/collective_ms.py`` restricted to a scope: the
run hands over ``extras["op_scopes"]``, the scope of each instruction of
the compiled step, and an event of the trace carries its instruction's
name.  A step is one host span named ``per``."""


def steps(record, per: str) -> int:
    """Host spans named ``per`` in the traced phase (0 without one)."""
    return 0 if record.trace is None else sum(
        1 for name, _, _ in record.trace.host if name == per)


def seconds(record, scope: str):
    """``(total, exposed)`` seconds of the whole traced phase, or None
    where there is nothing to read: no trace, no table of scopes, no
    collective under the scope."""
    from benchmarks import trace_reduce as tr

    scopes = record.extras.get("op_scopes")
    if record.trace is None or not record.trace.devices or not scopes:
        return None
    tot = exp = 0.0
    for dev in record.trace.devices.values():
        mine = {n for n in dev.collectives
                if scopes.get(n, (None,))[0] == scope}
        coll = tr.union([(a, b) for n, a, b in dev.sync + dev.spans
                         if n in mine])
        other = tr.union([(a, b) for n, a, b in dev.sync if n not in mine])
        tot += tr.length(coll)
        exp += tr.length(tr.subtract(coll, other))
    if not tot:
        return None
    n = len(record.trace.devices)
    return tot / n / 1e9, exp / n / 1e9


def read(record, args):
    n = steps(record, args["per"])
    found = seconds(record, args["scope"])
    if not n or found is None:
        return None
    total, exposed = found
    return 1e3 * {"total": total, "exposed": exposed}[args["part"]] / n
