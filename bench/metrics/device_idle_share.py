"""Share of the traced window in which no op ran on the device, in %:
1 - (union of the top-level op intervals / the window)."""


def read(ctx):
    t = ctx.trace
    w = t.window_s()
    return (1 - t.busy_s() / w) * 100 if w > 0 else None
