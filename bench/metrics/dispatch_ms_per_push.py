"""Host time of one call into the system (plan lookup, dispatch, enqueue)
until it returns: the mean length of the benchmark's ``bench.push``
spans in the traced window."""


def read(ctx):
    spans = ctx.trace.push_ns
    return sum(spans) / len(spans) * 1e-6 if spans else None
