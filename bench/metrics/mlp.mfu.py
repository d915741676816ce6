"""mlp.mfu: The whole step's model operations per second over the chips' bf16 peak, in %."""
import readers


def read(ctx):
    return readers.mfu(ctx)
