"""Device time per push of the pane-partial table's ``repro.window_emit``
stage (closed hopping windows folded by group and compacted; skipped by
a ``lax.cond`` on pushes that emit none), by self time, averaged over
every push of the traced window."""
import entry_stages


def read(ctx):
    return entry_stages.ms_per_push(ctx, "window_emit")
