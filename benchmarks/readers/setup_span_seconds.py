"""Seconds of set-up under the program's set-up spans named ``spans``
(spans closed while no ``Engine.step()`` was open: the constructor's
phases), summed: siblings, so none lies inside another.  Nothing to
read where the program keeps no set-up spans, a ring is full, or no
span of those names closed in set-up."""


def read(record, args):
    from benchmarks import program_setup

    cut = program_setup.cut(record)
    if cut is None:
        return None
    took = [t1 - t0 for name, t0, t1, *_ in cut["spans"]
            if name in args["spans"]]
    return sum(took) / 1e9 if took else None
