"""mlp.mac_fill: Useful ⊞-MACs over the ⊞-MACs the kernels' padded grids ran, from each launch's kernel_metadata extents, in %."""
import tags


def read(ctx):
    return tags.mac_fill(ctx)
