"""lm.mac_roofline: The least time the chip could take for the step's ⊞-MAC work over the kernels' device time, in %."""
import readers


def read(ctx):
    return readers.mac_roofline(ctx)
