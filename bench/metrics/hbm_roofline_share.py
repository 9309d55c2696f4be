"""The whole push's share of the HBM roofline, in %: the least time the
chip could take to read the push's input once and write its results once
(``bytes_per_push`` of the configuration's reference) at the peak
bandwidth of ``bench/peaks.json``, over the device's busy time per
push."""


def read(ctx):
    t = ctx.trace
    count = getattr(ctx.reference, "bytes_per_push", None)
    busy = t.busy_s() / t.pushes
    if count is None or busy <= 0:
        return None
    least = count(ctx.config, ctx.traffic) / ctx.peaks["hbm_bytes_per_s"]
    return least / busy * 100
