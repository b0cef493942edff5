"""A number the run recorded under ``key``."""


def read(record, args):
    return record.scalars.get(args["key"])
