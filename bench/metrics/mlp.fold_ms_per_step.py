"""mlp.fold_ms_per_step: Device time of the deterministic combine's fold (the launches with kernel_metadata kind boxsum) per step, in ms, per chip."""
import tags


def read(ctx):
    return tags.kind_ms_per_step(ctx, ("boxsum",))
