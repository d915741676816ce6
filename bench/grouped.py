"""The work of grouped ⊞-MAC launches: rows sorted by expert against
per-expert weights, as the expert layers of a mixture-of-experts model
run them.  A launch is ``(rows, K, N, G)``: ``rows`` routed rows in all,
each contracted over ``K`` against its expert's (K, N) weights, ``G``
experts' weights read."""
from __future__ import annotations

from roofline import WORD_BYTES

#: The ``kernel_metadata`` kinds of the grouped launches.
KINDS = ("gmm_fwd", "gmm_dx", "gmm_dw")


def ops(call) -> float:
    """Operations of one launch: a ⊞-MAC counts 2, as a multiply-add."""
    rows, k, n, _ = call
    return 2.0 * rows * k * n


def bytes_moved(call) -> float:
    """The least bytes one launch moves: its rows and results, and every
    expert's weights once, at the 16-bit LNS word."""
    rows, k, n, g = call
    return WORD_BYTES * (rows * k + g * k * n + rows * n)


def least_s(calls, pk: dict) -> float:
    """The least time the chip could take for these launches: per launch
    the larger of its operations at the bf16 peak and its bytes over HBM
    bandwidth."""
    return sum(max(ops(c) / pk["bf16_flops"],
                   bytes_moved(c) / pk["hbm_bytes_per_s"]) for c in calls)
