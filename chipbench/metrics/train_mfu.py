"""train_mfu: the whole step's share of the chips' peak bf16 rate.

Model FLOPs per token (the configuration's own ``flops_per_token``, from its
shapes, no recompute) x tokens per second of the traced window, over chips x
the peak of this device kind (``peaks.json``)."""


def read(ctx):
    cell, tr = ctx["cell"], ctx["trace"]
    flops = cell.model.flops_per_token(cell.config, cell.traffic["seq_len"])
    tokens_per_s = ctx["tokens_per_step"] * tr["steps"] / tr["window_s"]
    peak = len(ctx["devices"]) * ctx["peaks"]["bf16_flops"]
    return 100.0 * flops * tokens_per_s / peak
