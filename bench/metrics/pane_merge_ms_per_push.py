"""Device time per push of the window merge, the program's stage
``repro.pane_merge`` (``_pane_kernel``: each window merged from its
presorted panes, then the op tails), by self time."""
import stages


def read(ctx):
    return stages.ms_per_push(ctx, "pane_merge")
