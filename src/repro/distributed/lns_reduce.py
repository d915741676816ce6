"""Deterministic log-domain gradient all-reduce (the DP ⊞ contract).

Why a plain ``psum`` is wrong here: ⊞ (and float +, but we care about ⊞)
is only *approximately* associative, and XLA's all-reduce combines device
partials in a topology-dependent order.  For the paper's arithmetic the
accumulation order is part of the *semantics* — the sequential MAC order is
what the Pallas kernels, the emulation oracles, and every bit-exactness
test pin down.  A psum over per-device dW partials would therefore change
the weight codes whenever the device count (or the interconnect) changes,
silently breaking cross-backend bit-exactness.

The deterministic schedule used instead:

1. Each device emits **per-segment partial codes** for its slice of the
   canonical segmentation of the global batch (contiguous equal segments,
   numbered in batch order; a device owns a contiguous run of segments).
2. The partials are ``all_gather``-ed along the ``data`` axis with
   ``tiled=True`` — device order equals segment order, so the gathered
   leading axis is the canonical segment axis 0..S-1 on every device.
3. The S slots are ⊞-combined with a schedule that is a pure function of S
   (sequential left-fold by default), via ``core.arithmetic.boxsum_partials``
   or the ``lns_boxsum`` Pallas kernel (bit-exact to each other: the kernel
   walks its reduce axis sequentially).

Because neither the segmentation nor the combine schedule mentions the
device count, training on 1, 2, or 4 devices produces bit-identical codes
— device count only changes *where* a segment partial is computed.

``float_psum_allreduce`` is the fast non-bit-exact escape hatch: decode the
partials, let XLA psum them in float, re-encode.  Useful when throughput
matters more than the reduction-order contract; its result drifts from the
⊞ schedule by (bounded) approximation error, never catastrophically.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.arithmetic import boxsum_partials
from ..core.delta import DeltaEngine
from ..core.lns import LNSArray, decode, encode
from ..core.spec import REDUCE_MODES, REDUCE_SCHEDULES  # noqa: F401
from ..obs.trace import phase_scope
# (re-exported: the valid values live in core.spec, next to ReduceSpec —
# the serializable descriptor these semantics are selected by.)


def gather_partials(p: LNSArray, axis_name: str) -> LNSArray:
    """All-gather per-segment partials into canonical segment order.

    ``p``: (S_local, ...) partial codes on each device, segments in batch
    order.  Returns (S, ...) with S = S_local × axis size; ``tiled=True``
    concatenates along axis 0 in device order, which equals segment order
    because devices own contiguous runs of the batch (``P('data')`` shards
    contiguously).
    """
    code = jax.lax.all_gather(p.code, axis_name, axis=0, tiled=True)
    sign = jax.lax.all_gather(p.sign, axis_name, axis=0, tiled=True)
    return LNSArray(code, sign)


def dp_combine_blocks(n_elements: int, segments: int, eng: DeltaEngine, *,
                      blocks: str = "default",
                      interpret: bool | None = None):
    """The (block_m, block_k) tiles :func:`combine_partials` launches.

    Resolves the DP combine's fold shape exactly like the kernel path
    below: ``blocks="auto"`` consults the autotuner's op="boxsum" cache
    for the ``(elements, 1, S)`` reshaped fold (measured entries when one
    exists, the deterministic heuristic inside traces), an explicit
    ``MxNxK`` pins its M/K slots, ``"default"`` keeps the legacy fixed
    tiles (PR 5).  Tiling never changes results — this is the
    introspection hook DP bench rows record their chosen blocks through.
    """
    if blocks == "auto":
        from ..kernels import autotune
        bm, _, bk = autotune.lookup(
            "boxsum", (n_elements, 1, segments), fmt=eng.fmt,
            spec=eng.spec, interpret=interpret)
        return bm, bk
    from ..core.lns import resolve_blocks_arg
    bm, _, bk, _ = resolve_blocks_arg(
        blocks, min(256, n_elements), 1, segments)
    return bm, bk


def combine_partials(parts: LNSArray, eng: DeltaEngine, *,
                     schedule: str = "sequential",
                     use_kernel: bool = False,
                     interpret: bool | None = None,
                     blocks: str = "default") -> LNSArray:
    """⊞-combine (S, ...) stacked partials along axis 0, fixed schedule.

    ``use_kernel=True`` routes the sequential fold through the
    ``lns_boxsum`` Pallas kernel (reduce axis walked sequentially in-VMEM,
    bit-exact vs the jnp fold); the partial planes are reshaped to
    (elements, S) rows so one kernel launch reduces every weight entry.
    ``blocks`` is the spec's tiling axis for that launch:
    ``"auto"`` resolves the fold shape through the autotuner
    (op="boxsum"; :func:`dp_combine_blocks`), an explicit ``MxNxK``
    pins it, ``"default"`` keeps the legacy fixed tiles.  Blocks never
    change the combined codes — the kernel's reduce walk is sequential
    at any tiling — only the launch geometry.
    """
    if not use_kernel or schedule != "sequential":
        return boxsum_partials(parts, eng, schedule=schedule)
    from ..kernels.lns_boxsum import lns_boxsum_kernel
    s = parts.shape[0]
    tail = parts.shape[1:]
    code = parts.code.reshape(s, -1).T          # (elements, S)
    sign = parts.sign.reshape(s, -1).T
    n = code.shape[0]
    bm, bk = dp_combine_blocks(n, s, eng, blocks=blocks,
                               interpret=interpret)
    out = lns_boxsum_kernel(LNSArray(code, sign), fmt=eng.fmt,
                            spec=eng.spec, block_m=bm,
                            block_k=bk, interpret=interpret)
    return LNSArray(out.code.reshape(tail), out.sign.reshape(tail))


def deterministic_boxplus_allreduce(p: LNSArray, axis_name: str,
                                    eng: DeltaEngine, *,
                                    schedule: str = "sequential",
                                    use_kernel: bool = False,
                                    interpret: bool | None = None,
                                    blocks: str = "default") -> LNSArray:
    """The ⊞-allreduce: gather partials, combine with the fixed schedule.

    Must be called inside ``shard_map`` over ``axis_name``; every device
    returns the identical combined LNS gradient (replicated).  ``blocks``
    tiles the kernel combine (``"auto"`` = autotuned fold shapes) and
    never changes the combined codes.
    """
    with phase_scope("reduce/gather"):
        parts = gather_partials(p, axis_name)
    with phase_scope("reduce/fold"):
        return combine_partials(parts, eng, schedule=schedule,
                                use_kernel=use_kernel, interpret=interpret,
                                blocks=blocks)


def float_psum_allreduce(p: LNSArray, axis_name: str,
                         eng: DeltaEngine) -> LNSArray:
    """Escape hatch: decode partials → float psum → re-encode.

    Fast (one fused XLA all-reduce, no gather) but NOT bit-stable across
    device counts: float + is itself order-sensitive and the local segment
    partials are summed linearly rather than ⊞-combined.
    """
    fmt = eng.fmt
    local = jnp.sum(decode(p, fmt), axis=0)
    total = jax.lax.psum(local, axis_name)
    return encode(total, fmt)
