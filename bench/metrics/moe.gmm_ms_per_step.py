"""moe.gmm_ms_per_step: Device time of the grouped ⊞-MAC launches (kernel_metadata kind gmm_fwd, gmm_dx or gmm_dw) per step, in ms."""
import grouped
import tags


def read(ctx):
    return tags.kind_ms_per_step(ctx, grouped.KINDS)
