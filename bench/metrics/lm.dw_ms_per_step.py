"""lm.dw_ms_per_step: Device time of the dW ⊞-MAC launches (kernel_metadata kind dw, dw_update or dw_partials) per step, in ms."""
import tags


def read(ctx):
    return tags.kind_ms_per_step(ctx, tags.DW)
