"""What the per-layer metrics read from a traced window.  Each metric's
own file, ``bench/metrics/<name>.py``, names one of these; a reader that
finds nothing to read returns None and the metric is left out."""
from __future__ import annotations

import roofline
import trace as tr


def mfu(ctx):
    """The step's model operations per second over chips × bf16 peak, %."""
    return roofline.mfu(ctx.counts["model_ops"], ctx.steps,
                        ctx.trace.window_s, ctx.chips, ctx.peaks)


def mac_ms_per_step(ctx):
    """Device time of the ⊞-MAC kernels per step, ms (per chip)."""
    if not ctx.trace.count(tr.MAC):
        return None
    return 1e3 * ctx.trace.matched_s(tr.MAC) / ctx.steps


def mac_roofline(ctx):
    """The least time of the step's ⊞-MAC work over its kernel time, %."""
    if not ctx.trace.count(tr.MAC):
        return None
    least, _ = roofline.mac_least_s(ctx.counts["mac_calls"], ctx.peaks)
    return 100.0 * least * ctx.steps / ctx.trace.matched_s(tr.MAC)


def idle_share(ctx):
    """The share of the traced window in which no operation ran, %."""
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)


def collective_ms_per_step(ctx):
    """Device time of the collectives between chips per step, ms."""
    if not ctx.trace.count(tr.COLLECTIVE):
        return None
    return 1e3 * ctx.trace.matched_s(tr.COLLECTIVE) / ctx.steps
