"""Device time per push of the pane sort, the program's stage
``repro.sort_panes`` (``_sort_panes_kernel``: each pane sorted once), by
self time."""
import stages


def read(ctx):
    return stages.ms_per_push(ctx, "sort_panes")
