"""lm.idle_share: The share of the traced window in which the device ran nothing, in %."""
import readers


def read(ctx):
    return readers.idle_share(ctx)
