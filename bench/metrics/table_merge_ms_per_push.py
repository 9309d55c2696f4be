"""Device time per push of the pane-partial table's ``repro.table_merge``
stage (the binary search of the sorted table, the in-place fold of
matched rows and the one scatter that places kept and new rows), by self
time, loop control included."""
import entry_stages


def read(ctx):
    return entry_stages.ms_per_push(ctx, "table_merge")
