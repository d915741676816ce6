"""Standalone fused ⊞-SGD update kernel: ``(w, m, g) → (w', m')`` in one
pass.

This is the epilogue that *cannot* live in the dW kernel's flush: under
data parallelism the weight gradient only exists after the canonical
⊞-combine of the per-segment partials (``distributed/lns_reduce.py``), so
the deterministic-reduce contract requires the update to run **after** the
combine, on the already-replicated gradient.  This kernel is that step —
one elementwise pass applying ``M ← (μ ⊡ M) ⊞ G; W ← W ⊟ (LR ⊡ M) ⊟
(LRλ ⊡ W)`` — reused by
``distributed/lns_dp.py`` (via ``LNSMatmulBackend.fused_update``) and by
the bias updates of the fused single-device train step (bias gradients are
⊞-folds, not matmuls, so they have no dW flush to ride on).

Bit-exact against ``core.sgd.apply_update_codes`` (and therefore against
``core.sgd.apply_update`` when the epilogue came from
``UpdateEpilogue.from_sgd``): the flush math is shared with the dW-update
kernel (``_apply_update_epilogue``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ...core.delta import DeltaSpec
from ...core.formats import LNSFormat
from ...core.lns import resolve_interpret
from ...core.sgd import UpdateEpilogue
from .lns_matmul import (LANE, _apply_update_epilogue, delta_path,
                         make_delta_fn, tile, tiling)


def _update_kernel(*refs, fmt: LNSFormat, spec: DeltaSpec,
                   epilogue: UpdateEpilogue):
    refs = list(refs)
    has_mom = epilogue.momentum_code is not None
    wc_ref, ws_ref, gc_ref, gs_ref = refs[:4]
    pos = 4
    mc_ref = ms_ref = None
    if has_mom:
        mc_ref, ms_ref = refs[pos:pos + 2]
        pos += 2
    owc_ref, ows_ref = refs[pos:pos + 2]
    pos += 2
    omc_ref = oms_ref = None
    if has_mom:
        omc_ref, oms_ref = refs[pos:pos + 2]

    w_c, w_s, m_c, m_s = _apply_update_epilogue(
        wc_ref[...], ws_ref[...],
        mc_ref[...] if has_mom else None,
        ms_ref[...] if has_mom else None,
        gc_ref[...], gs_ref[...], epilogue,
        make_delta_fn(spec, fmt, gc_ref.shape[0]), fmt)
    owc_ref[...] = w_c
    ows_ref[...] = w_s
    if has_mom:
        omc_ref[...] = m_c
        oms_ref[...] = m_s


def lns_fused_update_pallas(w_code, w_sign, g_code, g_sign, *,
                            epilogue: UpdateEpilogue, fmt: LNSFormat,
                            spec: DeltaSpec, m_code=None, m_sign=None,
                            block: int = 8192,
                            interpret: Optional[bool] = None):
    """One-pass fused ⊞-SGD update over same-shape code/sign planes.

    Arbitrary-rank operands are flattened into lane-dense (rows, 128)
    planes, padded with the zero code, and updated about ``block``
    elements (whole rows; a multiple of 8 rows when compiled) per step of
    a 1-D grid (the op is purely elementwise, so tiling cannot change
    results).  Returns ``(w_code', w_sign')`` plus ``(m_code', m_sign')``
    when the epilogue has momentum.
    """
    interpret = resolve_interpret(interpret)
    has_mom = epilogue.momentum_code is not None
    if has_mom and (m_code is None or m_sign is None):
        raise ValueError("UpdateEpilogue has momentum but no momentum "
                         "planes (m_code/m_sign)")
    shape = w_code.shape
    n = max(1, int(np.prod(shape)))
    rows = -(-n // LANE)
    block_rows = tile(max(1, block // LANE), rows, tiling(interpret)[0])
    rows_p = -(-rows // block_rows) * block_rows
    pad = rows_p * LANE - n
    zc = np.int32(fmt.zero_code)

    def prep(code, sign):
        code = jnp.pad(code.reshape(-1), (0, pad), constant_values=zc)
        sign = jnp.pad(sign.reshape(-1), (0, pad))
        return code.reshape(rows_p, LANE), sign.reshape(rows_p, LANE)

    ins = list(prep(w_code, w_sign)) + list(prep(g_code, g_sign))
    if has_mom:
        ins += list(prep(m_code, m_sign))

    kernel = functools.partial(_update_kernel, fmt=fmt, spec=spec,
                               epilogue=epilogue)
    blk = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    n_out = 4 if has_mom else 2
    outs = pl.pallas_call(
        kernel,
        grid=(rows_p // block_rows,),
        in_specs=[blk] * len(ins),
        out_specs=[blk] * n_out,
        out_shape=[jax.ShapeDtypeStruct((rows_p, LANE), jnp.int32)
                   for _ in range(n_out)],
        interpret=interpret,
        metadata={"kind": "fused_update",
                  "delta": delta_path(spec, fmt)},
    )(*ins)
    return tuple(o.reshape(-1)[:n].reshape(shape) for o in outs)
