"""mlp.host_ms_per_step: Host time inside the program's repro.train_step span per step, in ms; None where the trace keeps no program spans."""
import tags


def read(ctx):
    return tags.host_ms_per_step(ctx)
