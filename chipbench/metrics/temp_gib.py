"""temp_gib: the temporaries of the jitted step as compiled for the chip, per
device (``memory_analysis().temp_size_in_bytes``)."""


def read(ctx):
    return ctx["temp_bytes"] / 2**30
