"""Model FLOP/s utilisation: the FLOP the forward and backward passes
require per token (recompute excluded, ``flops.train_flops_per_token``)
times tokens per second per chip, over the chip's bf16 peak."""


def read(record, args):
    rate = record.scalars.get(args["rate"])
    per_token = record.scalars.get("flop_per_token")
    if rate is None or per_token is None or not record.ctx.peaks:
        return None
    return 100.0 * per_token * rate / record.ctx.peaks["bf16_flops"]
