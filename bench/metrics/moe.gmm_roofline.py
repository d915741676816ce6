"""moe.gmm_roofline: The least time the chip could take for the step's expected grouped ⊞-MAC work (every held expert's weights read) over the grouped launches' device time, in %."""
import grouped
import tags


def read(ctx):
    k = tags.Kind(*grouped.KINDS)
    if not ctx.trace.count(k) or "gmm_calls" not in ctx.counts:
        return None
    least = grouped.least_s(ctx.counts["gmm_calls"], ctx.peaks)
    return 100.0 * least * ctx.steps / ctx.trace.matched_s(k)
