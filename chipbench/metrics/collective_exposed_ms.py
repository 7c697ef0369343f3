"""collective_exposed_ms: milliseconds a step in which a collective (an
all-gather, reduce-scatter, all-reduce, collective-permute or all-to-all)
runs on the busiest device with no other operation beside it: the
exchange between chips that the step waits for.  The trace's
``collective_exposed_s`` over the traced window's steps."""


def read(ctx):
    tr = ctx["trace"]
    return 1e3 * tr["collective_exposed_s"] / tr["steps"]
