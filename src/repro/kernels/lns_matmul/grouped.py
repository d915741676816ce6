"""Grouped ⊞-MAC: rows sorted by group against per-group weights.

The expert layer (``nn/moe.py``) sorts the token assignments that land on
the experts a device holds by expert, and runs each expert's rows against
that expert's weights.  Three products, as for the plain kernel:

* ``lns_gmm_pallas``     Y[r] = X[r] ⊞-MAC W[g(r)]         (forward)
* ``lns_gmm_dx_pallas``  dX[r] = dY[r] ⊞-MAC W[g(r)]ᵀ     (backward, rows)
* ``lns_gmm_dw_pallas``  dW[g] = X_gᵀ ⊞-MAC dY_g           (backward, weights)

``X`` is (M, K) with rows sorted by group: group ``g`` holds the
``sizes[g]`` rows after the rows of groups ``0 .. g-1``; rows past
``sum(sizes)`` belong to no group, read as zero and are returned as the
zero code.  ``W`` is (G, K, N).  Each product runs the plain kernel's
body (``_mac_steps``: the same ⊡ and the same ⊞, ``_boxplus_codes`` with
the Δ of ``make_delta_fn``), so a group's result is bit-identical to a
plain ⊞-MAC of its rows against its weights.

**Order.**  Forward and dX contract over K (resp. N) ascending, as the
plain kernel does.  dW_g contracts over group ``g``'s rows in the order
they are given — the expert layer gives them in ascending token order —
which is the order of a plain ``X_gᵀ ⊞-MAC dY_g``.  ⊞ is not associative,
so this order is part of the result.

**Layout.**  The wrapper gathers the rows into a padded layout in which
every group starts on a row tile and holds at least one tile (an empty
group's tile is all zero code, the ⊞ identity, so its dW is zero).  The
tile → group map and the number of tiles in use are scalar-prefetched;
the grid is sized statically by the row bound, ``ceil(M / b) + G`` tiles,
and a grid step past the last tile in use computes nothing and keeps
every block index where it was, so it moves no data: the time follows the
routed rows, not the bound.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.delta import DeltaSpec
from ...core.formats import LNSFormat
from ...core.lns import resolve_interpret
from .lns_matmul import (_mac_steps, _pad2, delta_path, make_delta_fn, tile,
                         tiling)


def group_layout(sizes, m: int, block: int):
    """Where each row goes in the padded layout of ``block``-row tiles.

    Returns ``(tile_group, n_used, src, dst)``: the group of each of the
    ``ceil(m / block) + G`` tiles (trailing unused tiles map to the last
    group), the number of tiles in use as a (1,) array, for each padded
    row its source row or -1, and for each of the ``m`` rows its padded
    row or -1 (rows past ``sum(sizes)``).
    """
    g = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    n_tiles = -(-m // block) + g
    tiles = jnp.maximum(1, (sizes + block - 1) // block)
    tile_end = jnp.cumsum(tiles)
    tile_start = tile_end - tiles
    t = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, t, side="right"), g - 1).astype(jnp.int32)
    row_end = jnp.cumsum(sizes)
    row_start = row_end - sizes
    p = jnp.arange(n_tiles * block, dtype=jnp.int32)
    pt = p // block
    pg = tile_group[pt]
    off = p - tile_start[pg] * block
    src = jnp.where((pt < tile_end[-1]) & (off < sizes[pg]),
                    row_start[pg] + off, -1)
    r = jnp.arange(m, dtype=jnp.int32)
    rg = jnp.minimum(jnp.searchsorted(row_end, r, side="right"), g - 1)
    dst = jnp.where(r < row_end[-1],
                    tile_start[rg] * block + r - row_start[rg], -1)
    return tile_group, tile_end[-1:].astype(jnp.int32), src, dst


def _take_rows(code, sign, idx, zero):
    """Rows ``idx`` of a (code, sign) pair; -1 gives a zero-code row."""
    ok = (idx >= 0)[:, None]
    j = jnp.clip(idx, 0, code.shape[0] - 1)
    return jnp.where(ok, code[j], zero), jnp.where(ok, sign[j], 0)


def _rows_kernel(tg_ref, nv_ref, ac_ref, as_ref, bc_ref, bs_ref, zc_ref,
                 zs_ref, accc_ref, accs_ref, *, fmt: LNSFormat,
                 spec: DeltaSpec, n_ct: int, b_ct: int):
    """Forward and dX: output row tile ``i`` is one group's, its B block
    that group's weights (the index map reads ``tg_ref``)."""
    del tg_ref
    s = pl.program_id(2)
    used = pl.program_id(0) < nv_ref[0]
    zero = np.int32(fmt.zero_code)

    @pl.when(used & (s == 0))
    def _init():
        accc_ref[...] = jnp.full_like(accc_ref, zero)
        accs_ref[...] = jnp.zeros_like(accs_ref)

    @pl.when(used)
    def _fold():
        delta = make_delta_fn(spec, fmt, accc_ref.shape[0])
        acc_c, acc_s = _mac_steps(ac_ref, as_ref, bc_ref, bs_ref,
                                  (accc_ref[...], accs_ref[...]), b_ct,
                                  delta, fmt)
        accc_ref[...] = acc_c
        accs_ref[...] = acc_s

    @pl.when(used & (s == n_ct - 1))
    def _flush():
        zc_ref[...] = accc_ref[...]
        zs_ref[...] = accs_ref[...]


def _dw_kernel(tg_ref, nv_ref, ac_ref, as_ref, bc_ref, bs_ref, zc_ref,
               zs_ref, accc_ref, accs_ref, *, fmt: LNSFormat,
               spec: DeltaSpec, b_ct: int, n_tiles: int):
    """dW: the contraction walks the row tiles in order; a group's tiles
    are consecutive, so its (K, N) block stays resident from its first
    tile, where the accumulator starts at zero, to its last, where it is
    flushed."""
    t = pl.program_id(2)
    nv = nv_ref[0]
    g = tg_ref[t]
    first = (t == 0) | (tg_ref[jnp.maximum(t - 1, 0)] != g)
    last = (t == nv - 1) | (tg_ref[jnp.minimum(t + 1, n_tiles - 1)] != g)
    used = t < nv
    zero = np.int32(fmt.zero_code)

    @pl.when(used & first)
    def _init():
        accc_ref[...] = jnp.full_like(accc_ref, zero)
        accs_ref[...] = jnp.zeros_like(accs_ref)

    @pl.when(used)
    def _fold():
        delta = make_delta_fn(spec, fmt, accc_ref.shape[0])
        acc_c, acc_s = _mac_steps(ac_ref, as_ref, bc_ref, bs_ref,
                                  (accc_ref[...], accs_ref[...]), b_ct,
                                  delta, fmt)
        accc_ref[...] = acc_c
        accs_ref[...] = acc_s

    @pl.when(used & last)
    def _flush():
        zc_ref[...] = accc_ref[...]
        zs_ref[...] = accs_ref[...]


def _metadata(kind, delta, **ext):
    return {"kind": kind, "delta": delta,
            **{k: str(v) for k, v in ext.items()}}


def _launch_rows(at_code, at_sign, w_code, w_sign, tile_group, n_used, *,
                 kind: str, m: int, block_rows: int, block_c: int,
                 block_ct: int, fmt: LNSFormat, spec: DeltaSpec,
                 interpret: bool):
    """Forward / dX launch.  ``at`` is (CT, Rp) in the padded row layout,
    ``w`` (G, CT, C) per-group contraction-major; returns (Rp, C)."""
    ct, rp = at_code.shape
    g, _, c = w_code.shape
    sub, lane = tiling(interpret)
    block_c = tile(block_c, c, lane)
    block_ct = tile(block_ct, ct, sub)
    zc = np.int32(fmt.zero_code)
    pad_c, pad_ct = (-c) % block_c, (-ct) % block_ct
    at_code, at_sign = _pad2(at_code, at_sign, pad_ct, 0, zc)
    if pad_c or pad_ct:
        pads = ((0, 0), (0, pad_ct), (0, pad_c))
        w_code = jnp.pad(w_code, pads, constant_values=zc)
        w_sign = jnp.pad(w_sign, pads)
    n_rt, n_c, n_ct = rp // block_rows, (c + pad_c) // block_c, \
        (ct + pad_ct) // block_ct

    def a_map(i, j, s, tg, nv):
        ok = i < nv[0]
        return (jnp.where(ok, s, n_ct - 1),
                jnp.where(ok, i, jnp.maximum(nv[0] - 1, 0)))

    def b_map(i, j, s, tg, nv):
        ok = i < nv[0]
        return (tg[i], jnp.where(ok, s, n_ct - 1), jnp.where(ok, j, n_c - 1))

    def o_map(i, j, s, tg, nv):
        ok = i < nv[0]
        return (jnp.where(ok, i, n_rt - 1), jnp.where(ok, j, n_c - 1))

    a_spec = pl.BlockSpec((block_ct, block_rows), a_map)
    b_spec = pl.BlockSpec((None, block_ct, block_c), b_map)
    o_spec = pl.BlockSpec((block_rows, block_c), o_map)
    kernel = functools.partial(_rows_kernel, fmt=fmt, spec=spec, n_ct=n_ct,
                               b_ct=block_ct)
    shape = jax.ShapeDtypeStruct((rp, c + pad_c), jnp.int32)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_rt, n_c, n_ct),
            in_specs=[a_spec, a_spec, b_spec, b_spec],
            out_specs=[o_spec, o_spec],
            scratch_shapes=[pltpu.VMEM((block_rows, block_c), jnp.int32),
                            pltpu.VMEM((block_rows, block_c), jnp.int32)]),
        out_shape=[shape, shape],
        interpret=interpret,
        metadata=_metadata(kind, delta_path(spec, fmt), g=g, r=m, c=c,
                           ct=ct, rp=rp, cp=c + pad_c, ctp=ct + pad_ct),
    )(tile_group, n_used, at_code, at_sign, w_code, w_sign)
    return tuple(o[:, :c] for o in out)


def _rows_block(block_rows: int, m: int, interpret: bool) -> int:
    """The row tile, which is also each group's alignment: on the chip a
    multiple of the lane width, since rows lie on lanes in forward/dX."""
    return tile(block_rows, m, tiling(interpret)[1])


def _rows_product(kind, a_code, a_sign, w_code, w_sign, sizes, *, fmt, spec,
                  block_rows, block_c, block_ct, interpret):
    """Rows ``a`` (M, CT) against per-group ``w`` (G, CT, C) → (M, C)."""
    interpret = resolve_interpret(interpret)
    m = a_code.shape[0]
    b = _rows_block(block_rows, m, interpret)
    tg, nv, src, dst = group_layout(sizes, m, b)
    zc = np.int32(fmt.zero_code)
    pc, ps = _take_rows(a_code, a_sign, src, zc)
    oc, os_ = _launch_rows(pc.T, ps.T, w_code, w_sign, tg, nv, kind=kind,
                           m=m, block_rows=b, block_c=block_c,
                           block_ct=block_ct, fmt=fmt, spec=spec,
                           interpret=interpret)
    return _take_rows(oc, os_, dst, zc)


def lns_gmm_pallas(x_code, x_sign, w_code, w_sign, sizes, *,
                   fmt: LNSFormat, spec: DeltaSpec, block_rows: int = 128,
                   block_n: int = 128, block_k: int = 128,
                   interpret: Optional[bool] = None):
    """Forward: x (M, K) rows sorted by group ⊞-MAC w (G, K, N) → (M, N)."""
    return _rows_product("gmm_fwd", x_code, x_sign, w_code, w_sign, sizes,
                         fmt=fmt, spec=spec, block_rows=block_rows,
                         block_c=block_n, block_ct=block_k,
                         interpret=interpret)


def lns_gmm_dx_pallas(dy_code, dy_sign, w_code, w_sign, sizes, *,
                      fmt: LNSFormat, spec: DeltaSpec, block_rows: int = 128,
                      block_k: int = 128, block_n: int = 128,
                      interpret: Optional[bool] = None):
    """Backward wrt rows: dY (M, N) ⊞-MAC W[g]ᵀ → dX (M, K), contraction
    over N ascending."""
    return _rows_product("gmm_dx", dy_code, dy_sign,
                         jnp.swapaxes(w_code, 1, 2),
                         jnp.swapaxes(w_sign, 1, 2),
                         sizes, fmt=fmt, spec=spec, block_rows=block_rows,
                         block_c=block_k, block_ct=block_n,
                         interpret=interpret)


def lns_gmm_dw_pallas(x_code, x_sign, dy_code, dy_sign, sizes, *,
                      fmt: LNSFormat, spec: DeltaSpec, block_rows: int = 128,
                      block_k: int = 128, block_n: int = 128,
                      interpret: Optional[bool] = None):
    """Backward wrt weights: dW[g] = X_gᵀ ⊞-MAC dY_g → (G, K, N), each
    group's rows contracted in the order given (ascending token order, as
    the expert layer sorts them); an empty group's dW is the zero code."""
    interpret = resolve_interpret(interpret)
    m, k = x_code.shape
    n = dy_code.shape[1]
    g = sizes.shape[0]
    lane = tiling(interpret)[1]
    b = _rows_block(block_rows, m, interpret)
    block_k = tile(block_k, k, lane)
    block_n = tile(block_n, n, lane)
    tg, nv, src, _ = group_layout(sizes, m, b)
    zc = np.int32(fmt.zero_code)
    xc, xs = _take_rows(x_code, x_sign, src, zc)
    dc, ds = _take_rows(dy_code, dy_sign, src, zc)
    pad_k, pad_n = (-k) % block_k, (-n) % block_n
    xc, xs = _pad2(xc, xs, 0, pad_k, zc)
    dc, ds = _pad2(dc, ds, 0, pad_n, zc)
    rp = xc.shape[0]
    n_t = rp // b

    def a_map(i, j, t, tg_, nv_):
        return (jnp.minimum(t, nv_[0] - 1), i)

    def b_map(i, j, t, tg_, nv_):
        return (jnp.minimum(t, nv_[0] - 1), j)

    def o_map(i, j, t, tg_, nv_):
        return (tg_[jnp.minimum(t, nv_[0] - 1)], i, j)

    a_spec = pl.BlockSpec((b, block_k), a_map)
    b_spec = pl.BlockSpec((b, block_n), b_map)
    o_spec = pl.BlockSpec((None, block_k, block_n), o_map)
    kernel = functools.partial(_dw_kernel, fmt=fmt, spec=spec, b_ct=b,
                               n_tiles=n_t)
    shape = jax.ShapeDtypeStruct((g, k + pad_k, n + pad_n), jnp.int32)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=((k + pad_k) // block_k, (n + pad_n) // block_n, n_t),
            in_specs=[a_spec, a_spec, b_spec, b_spec],
            out_specs=[o_spec, o_spec],
            scratch_shapes=[pltpu.VMEM((block_k, block_n), jnp.int32),
                            pltpu.VMEM((block_k, block_n), jnp.int32)]),
        out_shape=[shape, shape],
        interpret=interpret,
        metadata=_metadata("gmm_dw", delta_path(spec, fmt), g=g, r=k, c=n,
                           ct=m, rp=k + pad_k, cp=n + pad_n, ctp=rp),
    )(tg, nv, xc, xs, dc, ds)
    return tuple(o[:, :k, :n] for o in out)
