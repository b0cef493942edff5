"""How many samples the run recorded under ``samples``."""


def read(record, args):
    xs = record.samples.get(args["samples"])
    return None if xs is None else len(xs)
