"""mlp.step_idle_share: The share of the device's idle time that falls inside a repro.train_step span, in %; None where the trace keeps no program spans."""
import tags


def read(ctx):
    return tags.step_idle_share(ctx)
