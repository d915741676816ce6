"""mlp.collective_ms_per_step: device time of the collectives between
chips (the all-gather of the segment partials) per step, in ms."""
import readers


def read(ctx):
    return readers.collective_ms_per_step(ctx)
