"""Device time per push that no stage of the program claims: the self
time of the ops whose instruction carries no ``repro.<stage>`` scope (the
stages' coverage check)."""
import stages


def read(ctx):
    return stages.ms_per_push(ctx, None)
