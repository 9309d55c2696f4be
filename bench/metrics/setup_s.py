"""Seconds from the process's start to the end of warm-up: imports,
device start, data, plan and compile (from the persistent cache after a
checkout's first run), warm-up pushes."""


def read(ctx):
    return ctx.setup_s
