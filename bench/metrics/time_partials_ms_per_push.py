"""Device time per push of the pane-partial table's ``repro.time_partials``
stage (lateness by a cumulative max, pane ids, the sort by (group, pane)
and the segment reduction of the push), by self time."""
import entry_stages


def read(ctx):
    return entry_stages.ms_per_push(ctx, "time_partials")
