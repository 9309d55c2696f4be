"""Device time per push of the Pallas kernels (ops whose HLO is a
``tpu_custom_call``), by self time."""


def read(ctx):
    t = ctx.trace
    if not any(op.kernel for ops in t.ops for op in ops):
        return None
    return t.self_s(kernel=True) / t.pushes * 1e3
