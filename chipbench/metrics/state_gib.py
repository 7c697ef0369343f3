"""state_gib: bytes of the parameter and optimizer-state shards on the
fullest device, counted from the arrays after the window."""


def read(ctx):
    return ctx["state_bytes"] / 2**30
