"""lm.dx_ms_per_step: Device time of the dX ⊞-MAC launches (kernel_metadata kind dx) per step, in ms."""
import tags


def read(ctx):
    return tags.kind_ms_per_step(ctx, tags.DX)
