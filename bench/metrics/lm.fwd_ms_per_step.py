"""lm.fwd_ms_per_step: Device time of the forward ⊞-MAC launches (kernel_metadata kind fwd or fused_fwd) per step, in ms."""
import tags


def read(ctx):
    return tags.kind_ms_per_step(ctx, tags.FWD)
