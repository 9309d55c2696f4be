"""95th percentile over all pushes of the window of the time from a
push's submission to its results being ready, in ms."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx.window.latencies, 95)) * 1e3
