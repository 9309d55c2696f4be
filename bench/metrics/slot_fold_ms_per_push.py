"""Device time per push of the fold of per-slot partials into per-group
values, the program's stage ``repro.slot_fold``
(``kernels/swag/ops._combine_slot_partials``), by self time."""
import stages


def read(ctx):
    return stages.ms_per_push(ctx, "slot_fold")
