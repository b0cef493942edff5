"""How many compile-log records the metric's file names (``kinds``,
``cache``, ``under``: ``program_setup.chosen``) ended in set-up, or in
the measured window where ``where`` says ``window``.  Nothing to read
where the program keeps no compile log or a ring is full."""


def read(record, args):
    from benchmarks import program_setup

    cut = program_setup.cut(record)
    if cut is None:
        return None
    return len(program_setup.chosen(cut[args.get("where", "setup")], args))
