"""Device time per push of the per-tuple pane-store directory scan, the
program's stage ``repro.dir_scan`` (``core/swag.pergroup_write_plan``), by
self time, loop control included."""
import stages


def read(ctx):
    return stages.ms_per_push(ctx, "dir_scan")
