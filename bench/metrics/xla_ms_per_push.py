"""Device time per push of XLA's own ops (framing, sorts, scans, the
per-group directory scan and slot fold, output assembly): the self time
of every op that is not a Pallas kernel."""


def read(ctx):
    t = ctx.trace
    if not any(not op.kernel for ops in t.ops for op in ops):
        return None
    return t.self_s(kernel=False) / t.pushes * 1e3
