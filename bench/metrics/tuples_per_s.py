"""Tuples of all pushes completed in the window, over the window's
seconds (the window closes when the last push sent in it is ready)."""


def read(ctx):
    w = ctx.window
    return len(w.latencies) * ctx.traffic["push_tuples"] / w.seconds
