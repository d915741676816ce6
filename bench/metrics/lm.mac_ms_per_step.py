"""lm.mac_ms_per_step: Device time of the ⊞-MAC kernels per step, in ms, from the trace."""
import readers


def read(ctx):
    return readers.mac_ms_per_step(ctx)
