"""train_mfu_mesh4: the whole step's share of four chips' peak bf16 rate in
the four-chip mesh cell.  The number ``train_mfu``'s reader gives, under a
name of its own for the cells it is reported in; that reader computes it."""

from chipbench import cells


def read(ctx):
    return cells.metric_reader("train_mfu")(ctx)
