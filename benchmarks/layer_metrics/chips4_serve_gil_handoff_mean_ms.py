"""chips4_serve_gil_handoff_mean_ms: serve_gil_handoff_mean_ms's
arithmetic in the cell where each node compacts on a chip of its own: the
mean GIL hand-off from the release to the last completion, which says
whether the one interpreter, and not a device queue, sets the pace."""


def read(ctx):
    return ctx.load("layer_metrics", "serve_gil_handoff_mean_ms").read(ctx)
