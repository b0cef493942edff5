"""How often one of the program's kernels ran for a layer of a traced
training step: the ``XLA Ops`` events on the first chip whose name holds
the kernel's (``kernel``: a key of ``program.kernel_names()``), over the
mix's ``trace_steps`` times the configuration's ``num_hidden_layers``.
A flash forward reads 2 where a rematerialised block runs it again in
the backward pass and 1 where the block keeps the kernel's ``(out,
lse)``; between the two where some layers keep them.  Nothing to read
without a trace, or where the trace holds no such event.
"""


def read(record, args):
    from benchmarks import program, trace_reduce

    steps = record.ctx.traffic.get("trace_steps")
    layers = record.ctx.cfg.get("num_hidden_layers")
    if record.trace is None or not steps or not layers:
        return None
    events = trace_reduce.kernel_events(
        record.trace, program.kernel_names()[args["kernel"]])
    if not events:
        return None
    return len(events) / (steps * layers)
