"""Pallas TPU kernel for the ⊞-reduction (signed log-sum) along an axis.

This is the hardware hot-spot of the paper's soft-max block (eq. 14):
``Z = ⊞_j (codes_j, signs_j)``, and the combine of the data-parallel
⊞-allreduce.  The reduce dimension is walked sequentially in-kernel
(matching the paper's MAC ordering, bit-exact vs
core.arithmetic.boxsum(order="sequential")).

Layout: the (M, K) planes are transposed to (K, M), so each reduce step
reads one row at a dynamic sublane offset and the M rows being reduced
ride the lanes; one (1, bm) accumulator pair in VMEM scratch; K revisits
via the innermost grid axis.
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
from ..lns_matmul.lns_matmul import (_boxplus_codes, make_delta_fn,
                                     tile, tiling)


def _kernel(c_ref, s_ref, out_c_ref, out_s_ref, acc_c, acc_s, *,
            fmt: LNSFormat, spec: DeltaSpec, nk: int, bk: int):
    k_step = pl.program_id(1)

    @pl.when(k_step == 0)
    def _init():
        acc_c[...] = jnp.full_like(acc_c, np.int32(fmt.zero_code))
        acc_s[...] = jnp.zeros_like(acc_s)

    delta = make_delta_fn(spec, fmt)

    def body(i, carry):
        ac, asn = carry
        return _boxplus_codes(ac, asn, c_ref[pl.ds(i, 1), :],
                              s_ref[pl.ds(i, 1), :], delta, fmt)

    ac, asn = jax.lax.fori_loop(0, bk, body, (acc_c[...], acc_s[...]))
    acc_c[...] = ac
    acc_s[...] = asn

    @pl.when(k_step == nk - 1)
    def _flush():
        out_c_ref[...] = ac
        out_s_ref[...] = asn


def lns_boxsum_pallas(codes, signs, *, fmt: LNSFormat, spec: DeltaSpec,
                      block_m: int = 128, block_k: int = 128,
                      interpret: Optional[bool] = None):
    """⊞-reduce (M, K) int32 code/sign planes over axis 1 → (M,)."""
    interpret = resolve_interpret(interpret)
    m, k = codes.shape
    sub, lane = tiling(interpret)
    block_m = tile(block_m, m, lane)
    block_k = tile(block_k, k, sub)
    zc = np.int32(fmt.zero_code)
    pad_m = (-m) % block_m
    pad_k = (-k) % block_k
    codes = jnp.pad(codes.T, ((0, pad_k), (0, pad_m)), constant_values=zc)
    signs = jnp.pad(signs.T, ((0, pad_k), (0, pad_m)))
    kp, mp = codes.shape
    grid = (mp // block_m, kp // block_k)
    kernel = functools.partial(_kernel, fmt=fmt, spec=spec, nk=grid[1],
                               bk=block_k)
    in_spec = pl.BlockSpec((block_k, block_m), lambda i, kk: (kk, i))
    out_spec = pl.BlockSpec((1, block_m), lambda i, kk: (0, i))
    out_c, out_s = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[in_spec, in_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((1, mp), jnp.int32),
                   jax.ShapeDtypeStruct((1, mp), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((1, block_m), jnp.int32),
                        pltpu.VMEM((1, block_m), jnp.int32)],
        interpret=interpret,
        metadata={"kind": "boxsum"},
    )(codes, signs)
    return out_c[0, :m], out_s[0, :m]
