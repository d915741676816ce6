"""Jit'd public wrapper around the ⊞-reduction Pallas kernel."""
from __future__ import annotations

from functools import partial

import jax

from ...core.delta import DeltaSpec
from ...core.formats import LNSFormat
from ...core.lns import LNSArray, resolve_blocks_arg, resolve_interpret
from .lns_boxsum import lns_boxsum_pallas


@partial(jax.jit, static_argnames=("fmt", "spec", "block_m", "block_k",
                                   "interpret"))
def _call(codes, signs, fmt, spec, block_m, block_k, interpret):
    return lns_boxsum_pallas(codes, signs.astype("int32"), fmt=fmt,
                             spec=spec, block_m=block_m, block_k=block_k,
                             interpret=interpret)


def lns_boxsum_kernel(x: LNSArray, *, fmt: LNSFormat, spec: DeltaSpec,
                      block_m: int = 128, block_k: int = 128,
                      interpret: bool | None = None,
                      blocks: str = "default") -> LNSArray:
    """⊞-reduce an (M, K) LNSArray over axis 1 (the softmax Σ⊞).

    ``interpret=None`` resolves from the platform
    (``core.lns.resolve_interpret``: compiled on a TPU only).

    ``blocks`` is the spec's tiling axis: ``"auto"`` resolves
    (block_m, block_k) through the autotuner cache per shape
    (``kernels/autotune.py``, op ``"boxsum"``); an explicit ``"MxNxK"``
    pins block_m×block_k from its M/K slots; ``"default"`` keeps the
    keyword tile sizes.
    """
    interpret = resolve_interpret(interpret)
    if blocks == "auto":
        from .. import autotune
        block_m, _, block_k = autotune.lookup(
            "boxsum", (x.shape[0], 1, x.shape[1]), fmt=fmt, spec=spec,
            interpret=interpret)
    else:
        block_m, _, block_k, _ = resolve_blocks_arg(
            blocks, block_m, 1, block_k)
    code, sign = _call(x.code, x.sign, fmt, spec, block_m, block_k,
                       interpret)
    return LNSArray(code, sign.astype("int8"))
