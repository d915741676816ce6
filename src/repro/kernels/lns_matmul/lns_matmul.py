"""Pallas TPU kernels for the LNS ⊞-MAC matmul and its backward pass.

TPU adaptation of the paper's multiplication-free MAC: the MXU cannot be
used (there is no multiply to feed it); instead the max+Δ accumulation is
vectorized on the VPU over output tiles held in VMEM.  The LUT Δ± is a
compare-select over the table's breakpoints with the values baked in as
constants (``make_delta_fn``), bit-identical to ``DeltaEngine``.  The
contraction dimension is walked *sequentially* — the innermost grid axis
revisits the output tile, carrying the accumulator in VMEM scratch — which
reproduces the paper's sequential MAC ordering bit-exactly (see ref.py).

The entry points share one kernel body (``_mac_kernel``), parameterized
by an optional *flush-time epilogue*:

* ``lns_matmul_pallas``     Z[m,n]  = ⊞_k X[m,k] ⊡ W[k,n]   (forward, eq. 10)
* ``lns_matmul_dx_pallas``  dX[m,k] = ⊞_n dY[m,n] ⊡ W[k,n]  (= dY ⊞ Wᵀ)
* ``lns_matmul_dw_pallas``  dW[k,n] = ⊞_m X[m,k] ⊡ dY[m,n]  (= Xᵀ ⊞ dY)
* ``lns_matmul_fused_pallas``      forward with bias ⊞ / llrelu /
  requantize applied at accumulator flush (:class:`FwdEpilogue`)
* ``lns_matmul_dw_update_pallas``  dW with the ⊞-SGD update
  (momentum + weight decay) at flush — outputs are the updated weights
  (:class:`~repro.core.sgd.UpdateEpilogue`; see also ``update.py`` for
  the standalone elementwise variant the DP reduce applies post-combine)

The kernel takes both operands contraction-major, so every MAC step reads
one row of each block at a dynamic sublane offset.  The entry points pass
X (forward), dY and W (dX) transposed; dW's operands are stored that way
already.  Forward and backward matmuls run the same shifter/LUT datapath,
the hardware-shaped training path of Hamad et al. ("Bitwidth-Specific
Logarithmic Arithmetic for ... Training").

Compiled launches fit blocks to the chip's (8, 128) int32 tiling
(:func:`tile`): output rows and columns in multiples of 128, the
contraction in multiples of 8; interpret mode accepts any blocking.  VMEM
footprint per step ≈ 2·(b_r·b_c + b_r·b_ct + b_ct·b_c)·4 B; the default
(128, 128, 128) uses ≈ 0.5 MiB, leaving room for double-buffered
HBM→VMEM pipelining by the Mosaic compiler.

Signs are carried as int32 planes (0 = positive, 1 = negative): narrow int8
lanes buy nothing on the VPU and complicate tiling.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.delta import DeltaEngine, DeltaSpec
from ...core.formats import LNSFormat
from ...core.lns import resolve_interpret
from ...core.sgd import UpdateEpilogue


#: The TPU's native 32-bit tile, (sublanes, lanes).  A compiled kernel's
#: blocks have last two dims that are multiples of these, or whole axes.
SUBLANE, LANE = 8, 128


def tiling(interpret: bool) -> tuple:
    """``(sublane, lane)`` block alignment of a launch: the chip's tile
    when compiled, none in interpret mode."""
    return (1, 1) if interpret else (SUBLANE, LANE)


def tile(block: int, dim: int, align: int) -> int:
    """Tile edge along one axis of length ``dim``.

    A ``block`` at least as long as the axis covers it in one tile, padded
    up to ``align``; a shorter block must itself be a multiple of
    ``align`` (one of :func:`tiling`).  Padding uses
    the zero code, the ⊞ identity, so the tile never changes results.
    """
    if block >= dim:
        return -(-dim // align) * align
    if block % align:
        raise ValueError(
            f"block {block} over an axis of {dim} breaks the TPU tiling "
            f"rule: use a multiple of {align}, or a block that covers the "
            f"axis (interpret mode takes any block)")
    return block


def _lut_steps(eng: DeltaEngine):
    """The LUT Δ± as step functions of the integer d-code.

    ``DeltaEngine``'s nearest-sample lookup ``tab[(d + r//2) // r]`` (0
    past the table) is constant between the breakpoints ``j·r - r//2``.
    Returns ``(Δ+(0), Δ-(0), ((breakpoint, Δ+ or None, Δ- or None), ...))``
    keeping only the breakpoints where a value changes.
    """
    r = eng.r_code
    plus = [int(v) for v in eng._tab_plus] + [0]
    minus = [int(v) for v in eng._tab_minus] + [0]
    steps = []
    for j in range(1, len(plus)):
        p = plus[j] if plus[j] != plus[j - 1] else None
        m = minus[j] if minus[j] != minus[j - 1] else None
        if p is not None or m is not None:
            steps.append((j * r - r // 2, p, m))
    return plus[0], minus[0], tuple(steps)


def _delta_lut(d, same_sign, steps, underflow):
    """LUT Δ± by compare-select over the table's breakpoints.

    Bit-identical to ``DeltaEngine.plus`` / ``minus``; needs no gather,
    which the chip's compiler refuses on a 1-D table.
    """
    p0, m0, steps = steps
    dp = jnp.full(d.shape, p0, jnp.int32)
    dm = jnp.full(d.shape, m0, jnp.int32)
    for t, p, m in steps:
        ge = d >= t
        if p is not None:
            dp = jnp.where(ge, np.int32(p), dp)
        if m is not None:
            dm = jnp.where(ge, np.int32(m), dm)
    dm = jnp.where(d == 0, underflow, dm)
    return jnp.where(same_sign, dp, dm)


def _delta_exact(d, same_sign, scale, underflow):
    """Float-evaluated Δ± (oracle mode) — identical ops to DeltaEngine."""
    dp_f = d.astype(jnp.float32) / scale
    dp = jnp.round(jnp.log2(1.0 + jnp.exp2(-dp_f)) * scale).astype(jnp.int32)
    dm_f = jnp.maximum(d, 1).astype(jnp.float32) / scale
    ln2 = jnp.log(2.0).astype(jnp.float32)
    dm_val = jnp.log2(-jnp.expm1(-dm_f * ln2))
    dm = jnp.round(dm_val * scale).astype(jnp.int32)
    dm = jnp.where(d <= 0, underflow, dm)
    return jnp.where(same_sign, dp, dm)


def _delta_bitshift(d, same_sign, qf, underflow):
    """Eq. (9) bit-shift rule: Δ+ = 1>>⌊d⌋, Δ- = -(3>>(⌊d⌋+1)) in code units."""
    d_int = jnp.minimum(d >> qf, 30)
    dp = jnp.int32(1 << qf) >> d_int
    dm = -(jnp.int32(3 << qf) >> (d_int + 1))
    dm = jnp.where(d == 0, underflow, dm)
    return jnp.where(same_sign, dp, dm)


def _boxplus_codes(ac, asn, bc, bsn, delta_fn, fmt: LNSFormat):
    """⊞ on raw (code, sign) planes — mirrors core.arithmetic.boxplus."""
    zero = np.int32(fmt.zero_code)
    za = ac == zero
    zb = bc == zero
    m = jnp.maximum(ac, bc)
    d = jnp.abs(ac - bc)
    same = asn == bsn
    delta = delta_fn(d, same)
    code = jnp.minimum(m + delta, fmt.code_max)
    code = jnp.where(code < fmt.min_nonzero_code, zero, code)
    cancel = (~same) & (d == 0)
    code = jnp.where(cancel, zero, code)
    sign = jnp.where(same, asn, jnp.where(ac > bc, asn, bsn))
    code = jnp.where(za, bc, jnp.where(zb, ac, code))
    sign = jnp.where(za, bsn, jnp.where(zb, asn, sign))
    sign = jnp.where(code == zero, 0, sign)
    return code, sign


def make_delta_fn(spec: DeltaSpec, fmt: LNSFormat):
    """In-kernel Δ±(d, same_sign) on integer d-codes for one (Δ, format).

    Every kind is plain elementwise integer (or float, for ``exact``) work
    with its constants baked in at trace time: no table operand.
    """
    eng = DeltaEngine(spec, fmt)
    underflow = np.int32(eng.underflow)
    if spec.kind == "bitshift":
        return lambda d, same: _delta_bitshift(d, same, qf=fmt.qf,
                                               underflow=underflow)
    if spec.kind == "exact":
        return lambda d, same: _delta_exact(d, same, scale=fmt.scale,
                                            underflow=underflow)
    steps = _lut_steps(eng)
    return lambda d, same: _delta_lut(d, same, steps, underflow)


# ------------------------------------------------------------------------
# Flush-time epilogues
# ------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FwdEpilogue:
    """Flush-time epilogue of the forward ⊞-MAC kernel.

    Applied to the final accumulator tile in the order the unfused train
    step applies the same ops as separate XLA passes:

    1. ``bias=True``           — ⊞-add a broadcast (N,) bias row;
    2. ``llrelu_beta=β``       — log-leaky-ReLU (code += β on negatives,
                                 underflow flush; ``core.activations.llrelu``);
    3. ``dst_fmt=<LNSFormat>`` — requantize onto another format's code grid
                                 (the barrel shift of
                                 ``core.lns.convert_format``), so a layer
                                 whose output crosses a NumericsPlan format
                                 boundary emits codes already in the target
                                 format — no separate conversion pass.

    ``emit_z_sign=True`` adds one extra output plane carrying the
    *post-bias, pre-activation* sign — the only piece of z the backward
    pass needs (``llrelu_grad`` depends on sign(z) alone).

    Frozen/hashable: usable as a static kernel parameter.
    """

    bias: bool = False
    llrelu_beta: Optional[int] = None
    dst_fmt: Optional[LNSFormat] = None
    emit_z_sign: bool = False

    @property
    def is_noop(self) -> bool:
        return (not self.bias and self.llrelu_beta is None
                and self.dst_fmt is None and not self.emit_z_sign)


def _apply_fwd_epilogue(code, sign, ep: FwdEpilogue, bias_c, bias_s,
                        delta_fn, fmt: LNSFormat):
    """bias ⊞ → llrelu → requantize on raw code/sign planes.

    Each step mirrors its unfused counterpart (``core.arithmetic.bias_add``,
    ``core.activations.llrelu``, ``core.lns.convert_format``) op-for-op, so
    the fused flush is bit-identical to the separate-pass composition.
    Returns ``(code, sign, z_sign)`` with ``z_sign`` the post-bias sign.
    """
    zero = np.int32(fmt.zero_code)
    if ep.bias:
        code, sign = _boxplus_codes(code, sign, bias_c, bias_s, delta_fn,
                                    fmt)
    z_sign = sign
    if ep.llrelu_beta is not None:
        shifted = code + np.int32(ep.llrelu_beta)
        shifted = jnp.where(shifted < fmt.min_nonzero_code, zero, shifted)
        act = jnp.where(sign == 1, shifted, code)
        code = jnp.where(code == zero, zero, act)
    if ep.dst_fmt is not None and ep.dst_fmt != fmt:
        dst = ep.dst_fmt
        shift = dst.qf - fmt.qf
        if shift >= 0:
            conv = code << shift
        else:
            conv = (code + (1 << (-shift - 1))) >> (-shift)
        under = conv < dst.min_nonzero_code
        conv = jnp.clip(conv, dst.min_nonzero_code, dst.code_max)
        is_zero = (code == zero) | under
        code = jnp.where(is_zero, np.int32(dst.zero_code), conv)
        sign = jnp.where(is_zero, 0, sign)
    return code, sign, z_sign


def _scalar_boxdot_codes(scode: int, t_c, t_s, fmt: LNSFormat):
    """⊡ by a positive scalar code — mirrors ``core.arithmetic.boxdot``.

    The scalar is a nonzero positive constant (``scalar()`` never yields
    the zero sentinel), so only the tensor operand's zeros propagate.
    """
    zero = np.int32(fmt.zero_code)
    zt = t_c == zero
    code = jnp.minimum(t_c + np.int32(scode), fmt.code_max)
    code = jnp.where(code < fmt.min_nonzero_code, zero, code)
    code = jnp.where(zt, zero, code)
    sign = jnp.where(zt, 0, t_s)
    return code, sign


def _apply_update_epilogue(w_c, w_s, m_c, m_s, g_c, g_s,
                           ep: UpdateEpilogue, delta_fn, fmt: LNSFormat):
    """⊞-SGD at flush — mirrors ``core.sgd.apply_update_codes`` op-for-op.

    ``g`` is the just-flushed gradient accumulator; ``w``/``m`` are the
    resident weight/momentum tiles.  Returns the updated
    ``(w_c, w_s, m_c, m_s)`` planes (momentum planes pass through
    untouched when the epilogue has no momentum term).
    """
    if ep.momentum_code is not None:
        mm_c, mm_s = _scalar_boxdot_codes(ep.momentum_code, m_c, m_s, fmt)
        m_c, m_s = _boxplus_codes(mm_c, mm_s, g_c, g_s, delta_fn, fmt)
        g_c, g_s = m_c, m_s
    lg_c, lg_s = _scalar_boxdot_codes(ep.lr_code, g_c, g_s, fmt)
    w_c, w_s = _boxplus_codes(w_c, w_s, lg_c, lg_s ^ 1, delta_fn, fmt)
    if ep.weight_decay_code is not None:
        wd_c, wd_s = _scalar_boxdot_codes(ep.weight_decay_code, w_c, w_s,
                                          fmt)
        w_c, w_s = _boxplus_codes(w_c, w_s, wd_c, wd_s ^ 1, delta_fn, fmt)
    return w_c, w_s, m_c, m_s


def _mac_steps(ac_ref, as_ref, bc_ref, bs_ref, acc, b_ct: int, delta,
               fmt: LNSFormat):
    """The sequential ⊞-MAC over one contraction tile, from ``acc``.

    Both operands arrive contraction-major: A as a (b_ct, b_r) block, B as
    (b_ct, b_c).  Step ``i`` of the fori_loop reads row ``i`` of each
    through its ref (a dynamic sublane offset, which the chip's compiler
    accepts where a dynamic lane slice is refused), turns A's row into a
    (b_r, 1) column and ⊞-accumulates the (b_r, b_c) outer product.
    Returns the ``(code, sign)`` accumulator planes.
    """
    zero = np.int32(fmt.zero_code)
    b_r = ac_ref.shape[1]

    def body(i, carry):
        acc_c, acc_s = carry
        # Contraction step i of this tile: (b_r, 1) ⊡ (1, b_c).
        a_c = ac_ref[pl.ds(i, 1), :].reshape(b_r, 1)
        a_s = as_ref[pl.ds(i, 1), :].reshape(b_r, 1)
        b_c = bc_ref[pl.ds(i, 1), :]
        b_s = bs_ref[pl.ds(i, 1), :]
        pc = a_c + b_c
        pz = (a_c == zero) | (b_c == zero)
        pc = jnp.minimum(pc, fmt.code_max)
        pc = jnp.where(pc < fmt.min_nonzero_code, zero, pc)
        pc = jnp.where(pz, zero, pc)
        ps = jnp.where(pz, 0, a_s ^ b_s)
        return _boxplus_codes(acc_c, acc_s, pc, ps, delta, fmt)

    return jax.lax.fori_loop(0, b_ct, body, acc)


def _mac_kernel(*refs, fmt: LNSFormat, spec: DeltaSpec, n_ct: int, b_ct: int,
                partial_flush: bool = False,
                fwd_epilogue: Optional[FwdEpilogue] = None,
                update_epilogue: Optional[UpdateEpilogue] = None):
    """Generic sequential ⊞-MAC over one contraction tile (:func:`_mac_steps`).

    ``partial_flush=True`` turns the kernel into a *segment-partial* MAC:
    the accumulator is re-initialized at every contraction block and each
    block's ⊞-fold is flushed to its own output slot ``out[s]`` instead of
    carrying across blocks — the per-segment partial codes that the
    data-parallel deterministic ⊞-allreduce combines across devices
    (``distributed/lns_reduce.py``).

    The epilogues run **at accumulator flush only** (the contract of the
    fused subsystem, see ROADMAP §Fused epilogues): ``fwd_epilogue``
    applies bias ⊞ / llrelu / requantize to the final forward accumulator;
    ``update_epilogue`` turns the dW flush into the ⊞-SGD update — the
    outputs become the *updated* weight (+ momentum) codes and the raw dW
    never round-trips through memory.  Both are mutually exclusive with
    ``partial_flush`` (segment partials feed the DP ⊞-combine first; their
    epilogue is the standalone fused-update kernel).

    The ref layout (built by ``_launch_mac``) is:
    ``A, B, [bias], [w], [m], out, [z_sign], [m_out], acc``
    with each logical operand a (code, sign) pair of refs.
    """
    refs = list(refs)
    ac_ref, as_ref, bc_ref, bs_ref = refs[:4]
    pos = 4
    has_bias = fwd_epilogue is not None and fwd_epilogue.bias
    emit_z_sign = fwd_epilogue is not None and fwd_epilogue.emit_z_sign
    has_update = update_epilogue is not None
    has_mom = has_update and update_epilogue.momentum_code is not None
    biasc_ref = biass_ref = None
    if has_bias:
        biasc_ref, biass_ref = refs[pos:pos + 2]
        pos += 2
    wc_ref = ws_ref = mc_ref = ms_ref = None
    if has_update:
        wc_ref, ws_ref = refs[pos:pos + 2]
        pos += 2
        if has_mom:
            mc_ref, ms_ref = refs[pos:pos + 2]
            pos += 2
    zc_ref, zs_ref = refs[pos:pos + 2]
    pos += 2
    zsign_ref = None
    if emit_z_sign:
        zsign_ref = refs[pos]
        pos += 1
    omc_ref = oms_ref = None
    if has_mom:
        omc_ref, oms_ref = refs[pos:pos + 2]
        pos += 2
    accc_ref, accs_ref = refs[pos:pos + 2]

    ct_step = pl.program_id(2)

    if partial_flush:
        # Every contraction block is its own segment: fresh accumulator.
        accc_ref[...] = jnp.full_like(accc_ref, np.int32(fmt.zero_code))
        accs_ref[...] = jnp.zeros_like(accs_ref)
    else:
        @pl.when(ct_step == 0)
        def _init():
            accc_ref[...] = jnp.full_like(accc_ref, np.int32(fmt.zero_code))
            accs_ref[...] = jnp.zeros_like(accs_ref)

    delta = make_delta_fn(spec, fmt)
    acc_c, acc_s = _mac_steps(ac_ref, as_ref, bc_ref, bs_ref,
                              (accc_ref[...], accs_ref[...]), b_ct, delta,
                              fmt)
    accc_ref[...] = acc_c
    accs_ref[...] = acc_s

    if partial_flush:
        # Output block (1, b_r, b_c) is this segment's slot: flush always.
        zc_ref[0, :, :] = acc_c
        zs_ref[0, :, :] = acc_s
    else:
        @pl.when(ct_step == n_ct - 1)
        def _flush():
            out_c, out_s = acc_c, acc_s
            if fwd_epilogue is not None:
                out_c, out_s, z_sign = _apply_fwd_epilogue(
                    out_c, out_s, fwd_epilogue,
                    biasc_ref[...] if has_bias else None,
                    biass_ref[...] if has_bias else None, delta, fmt)
                if emit_z_sign:
                    zsign_ref[...] = z_sign
            if has_update:
                out_c, out_s, m_c, m_s = _apply_update_epilogue(
                    wc_ref[...], ws_ref[...],
                    mc_ref[...] if has_mom else None,
                    ms_ref[...] if has_mom else None,
                    out_c, out_s, update_epilogue, delta, fmt)
                if has_mom:
                    omc_ref[...] = m_c
                    oms_ref[...] = m_s
            zc_ref[...] = out_c
            zs_ref[...] = out_s


def _pad2(code, sign, pad_r, pad_c, zero):
    if pad_r or pad_c:
        code = jnp.pad(code, ((0, pad_r), (0, pad_c)), constant_values=zero)
        sign = jnp.pad(sign, ((0, pad_r), (0, pad_c)))
    return code, sign


def _launch_mac(at_code, at_sign, b_code, b_sign, *, kind: str,
                fmt: LNSFormat, spec: DeltaSpec, block_r: int, block_c: int,
                block_ct: int, interpret: bool, partial_flush: bool = False,
                fwd_epilogue: Optional[FwdEpilogue] = None,
                bias_code=None, bias_sign=None,
                update_epilogue: Optional[UpdateEpilogue] = None,
                w_code=None, w_sign=None, m_code=None, m_sign=None):
    """Shared pallas_call launcher for the three ⊞-MAC kernels.

    Both operands are contraction-major: ``at`` is (CT, R) and produces
    the output rows, ``b`` is (CT, C) and produces the output columns.
    R/C/CT need not be multiples of the block sizes (inputs are padded with
    the zero code, which is the ⊞ identity); compiled launches fit the
    blocks to the chip's (8, 128) tiling with :func:`tile`.

    With ``partial_flush=True`` the contraction is *not* carried across CT
    blocks: the call returns ``(n_ct, R, C)`` per-segment partials, one slot
    per contraction block of ``block_ct`` rows (see ``_mac_kernel``).

    ``fwd_epilogue`` (with an optional (C,) ``bias_code``/``bias_sign``)
    and ``update_epilogue`` (with (R, C) ``w_*`` and optional ``m_*``
    planes) select the flush-time epilogue; outputs grow accordingly
    (z_sign plane / updated-momentum planes) and the return is a tuple of
    all cropped output planes in kernel order.

    ``kind`` names the launch in the profiler's trace: the custom call
    carries ``kernel_metadata`` with it and the launch's extents, output
    rows ``r``, columns ``c`` and contraction depth ``ct`` as given and
    ``rp``/``cp``/``ctp`` as padded for the grid, so a trace reader can
    tell forward from backward and useful ⊞-MACs from padding.
    """
    if partial_flush and (fwd_epilogue is not None
                          or update_epilogue is not None):
        raise ValueError(
            "flush epilogues do not compose with partial_flush: segment "
            "partials feed the DP ⊞-combine first; apply the fused update "
            "after the combine (kernels/lns_matmul/update.py)")
    if fwd_epilogue is not None and update_epilogue is not None:
        raise ValueError("at most one flush epilogue per kernel launch")
    ct, r = at_code.shape
    ct2, c = b_code.shape
    assert ct == ct2, (at_code.shape, b_code.shape)
    sub, lane = tiling(interpret)
    block_r = tile(block_r, r, lane)
    block_c = tile(block_c, c, lane)
    block_ct = tile(block_ct, ct, sub)

    zc = np.int32(fmt.zero_code)
    pad_r = (-r) % block_r
    pad_c = (-c) % block_c
    pad_ct = (-ct) % block_ct
    at_code, at_sign = _pad2(at_code, at_sign, pad_ct, pad_r, zc)
    b_code, b_sign = _pad2(b_code, b_sign, pad_ct, pad_c, zc)
    a_spec = pl.BlockSpec((block_ct, block_r), lambda i, j, s: (s, i))
    b_spec = pl.BlockSpec((block_ct, block_c), lambda i, j, s: (s, j))

    rp, cp, ctp = r + pad_r, c + pad_c, ct + pad_ct
    grid = (rp // block_r, cp // block_c, ctp // block_ct)

    kernel = functools.partial(
        _mac_kernel, fmt=fmt, spec=spec, n_ct=grid[2], b_ct=block_ct,
        partial_flush=partial_flush, fwd_epilogue=fwd_epilogue,
        update_epilogue=update_epilogue)

    out_block = pl.BlockSpec((block_r, block_c), lambda i, j, s: (i, j))

    extra_in, extra_in_specs = [], []
    if fwd_epilogue is not None and fwd_epilogue.bias:
        if bias_code is None or bias_sign is None:
            raise ValueError("FwdEpilogue(bias=True) needs bias_code/"
                             "bias_sign")
        bias_code = jnp.pad(bias_code.reshape(1, -1), ((0, 0), (0, pad_c)),
                            constant_values=zc)
        bias_sign = jnp.pad(bias_sign.reshape(1, -1), ((0, 0), (0, pad_c)))
        bias_spec = pl.BlockSpec((1, block_c), lambda i, j, s: (0, j))
        extra_in += [bias_code, bias_sign]
        extra_in_specs += [bias_spec, bias_spec]
    if update_epilogue is not None:
        if w_code is None or w_sign is None:
            raise ValueError("an UpdateEpilogue needs the resident weight "
                             "planes (w_code/w_sign)")
        w_code, w_sign = _pad2(w_code, w_sign, pad_r, pad_c, zc)
        extra_in += [w_code, w_sign]
        extra_in_specs += [out_block, out_block]
        if update_epilogue.momentum_code is not None:
            if m_code is None or m_sign is None:
                raise ValueError("UpdateEpilogue has momentum but no "
                                 "momentum planes (m_code/m_sign)")
            m_code, m_sign = _pad2(m_code, m_sign, pad_r, pad_c, zc)
            extra_in += [m_code, m_sign]
            extra_in_specs += [out_block, out_block]

    if partial_flush:
        out_shape = [
            jax.ShapeDtypeStruct((grid[2], rp, cp), jnp.int32),
            jax.ShapeDtypeStruct((grid[2], rp, cp), jnp.int32),
        ]
        out_specs = [
            pl.BlockSpec((1, block_r, block_c), lambda i, j, s: (s, i, j)),
            pl.BlockSpec((1, block_r, block_c), lambda i, j, s: (s, i, j)),
        ]
    else:
        n_extra_out = (
            (1 if fwd_epilogue is not None and fwd_epilogue.emit_z_sign
             else 0)
            + (2 if update_epilogue is not None
               and update_epilogue.momentum_code is not None else 0))
        out_shape = [jax.ShapeDtypeStruct((rp, cp), jnp.int32)
                     for _ in range(2 + n_extra_out)]
        out_specs = [out_block for _ in range(2 + n_extra_out)]
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[a_spec, a_spec, b_spec, b_spec] + extra_in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_r, block_c), jnp.int32),
            pltpu.VMEM((block_r, block_c), jnp.int32),
        ],
        interpret=interpret,
        metadata={"kind": kind, **{k: str(v) for k, v in dict(
            r=r, c=c, ct=ct, rp=rp, cp=cp, ctp=ctp).items()}},
    )(at_code, at_sign, b_code, b_sign, *extra_in)
    if partial_flush:
        return tuple(o[:, :r, :c] for o in outs)
    return tuple(o[:r, :c] for o in outs)


def lns_matmul_pallas(x_code, x_sign, w_code, w_sign, *,
                      fmt: LNSFormat, spec: DeltaSpec,
                      block_m: int = 128, block_n: int = 128,
                      block_k: int = 128, interpret: Optional[bool] = None):
    """Forward: x (M, K) ⊞-MAC w (K, N) → (M, N), sequential over K."""
    return _launch_mac(x_code.T, x_sign.T, w_code, w_sign, kind="fwd",
                       fmt=fmt, spec=spec, block_r=block_m, block_c=block_n,
                       block_ct=block_k,
                       interpret=resolve_interpret(interpret))


def lns_matmul_dx_pallas(dy_code, dy_sign, w_code, w_sign, *,
                         fmt: LNSFormat, spec: DeltaSpec,
                         block_m: int = 128, block_k: int = 128,
                         block_n: int = 128,
                         interpret: Optional[bool] = None):
    """Backward wrt activations: dY (M, N) ⊞-MAC Wᵀ → dX (M, K).

    The contraction walks N sequentially (ascending), matching
    ``lns_matmul(dY, Wᵀ)`` with ``order="sequential"`` bit-exactly.
    """
    return _launch_mac(dy_code.T, dy_sign.T, w_code.T, w_sign.T, kind="dx",
                       fmt=fmt, spec=spec, block_r=block_m, block_c=block_k,
                       block_ct=block_n,
                       interpret=resolve_interpret(interpret))


def lns_matmul_dw_pallas(x_code, x_sign, dy_code, dy_sign, *,
                         fmt: LNSFormat, spec: DeltaSpec,
                         block_k: int = 128, block_n: int = 128,
                         block_m: int = 128,
                         interpret: Optional[bool] = None):
    """Backward wrt weights: Xᵀ ⊞-MAC dY (M, N) → dW (K, N).

    X and dY are read in their stored (M, ·) layout, already
    contraction-major; the contraction walks the batch dimension M
    sequentially (ascending), matching ``lns_matmul(Xᵀ, dY)`` with
    ``order="sequential"`` bit-exactly.
    """
    return _launch_mac(x_code, x_sign, dy_code, dy_sign, kind="dw",
                       fmt=fmt, spec=spec, block_r=block_k, block_c=block_n,
                       block_ct=block_m,
                       interpret=resolve_interpret(interpret))


def _pad_segments(a, num_segments: int, pad: int, fill):
    """(S·seg, N) → (S·(seg + pad), N), ``pad`` fill rows after each
    segment."""
    seg = a.shape[0] // num_segments
    a = a.reshape(num_segments, seg, -1)
    a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=fill)
    return a.reshape(num_segments * (seg + pad), -1)


def lns_matmul_dw_partials_pallas(x_code, x_sign, dy_code, dy_sign, *,
                                  num_segments: int, fmt: LNSFormat,
                                  spec: DeltaSpec, block_k: int = 128,
                                  block_n: int = 128,
                                  interpret: Optional[bool] = None):
    """Backward-weight kernel with per-segment partial-code flush.

    The batch M is split into ``num_segments`` equal contiguous segments
    (M must divide exactly); segment ``s`` covers rows
    ``[s·M/S, (s+1)·M/S)``.  Returns ``(S, K, N)`` code/sign planes where
    ``out[s] = X[seg_s]ᵀ ⊞-MAC dY[seg_s]`` with the same ascending
    sequential MAC order *within* the segment as ``lns_matmul_dw_pallas``.
    The partials are what the data-parallel deterministic ⊞-allreduce
    combines in canonical segment order — combining them sequentially
    reproduces the single-device sequential MAC schedule over the canonical
    segmentation regardless of how segments are assigned to devices.

    Each segment is padded with zero-code rows (the ⊞ identity, after
    the segment's own rows) to a multiple of 8 rows, so any segment size
    tiles on the chip.
    """
    m = x_code.shape[0]
    if num_segments < 1 or m % num_segments:
        raise ValueError(
            f"batch {m} not divisible into {num_segments} equal segments")
    seg = m // num_segments
    pad = (-seg) % SUBLANE
    if pad:
        zc = np.int32(fmt.zero_code)
        x_code = _pad_segments(x_code, num_segments, pad, zc)
        x_sign = _pad_segments(x_sign, num_segments, pad, 0)
        dy_code = _pad_segments(dy_code, num_segments, pad, zc)
        dy_sign = _pad_segments(dy_sign, num_segments, pad, 0)
    return _launch_mac(x_code, x_sign, dy_code, dy_sign, kind="dw_partials",
                       fmt=fmt, spec=spec, block_r=block_k, block_c=block_n,
                       block_ct=seg + pad,
                       interpret=resolve_interpret(interpret),
                       partial_flush=True)


def lns_matmul_fused_pallas(x_code, x_sign, w_code, w_sign, *,
                            fmt: LNSFormat, spec: DeltaSpec,
                            epilogue: FwdEpilogue,
                            bias_code=None, bias_sign=None,
                            block_m: int = 128, block_n: int = 128,
                            block_k: int = 128,
                            interpret: Optional[bool] = None):
    """Forward ⊞-MAC with the flush-time epilogue (bias ⊞ / llrelu /
    requantize) applied to the final accumulator — one pass instead of
    matmul + three separate elementwise passes.

    Returns ``(z_code, z_sign)``, plus a trailing ``z_sign`` plane (the
    post-bias pre-activation sign) when ``epilogue.emit_z_sign``.  With
    ``epilogue.dst_fmt`` set the output codes are already on the target
    format's grid.  Bit-exact against ``ref.lns_matmul_fused_ref``, the
    unfused composition.
    """
    return _launch_mac(x_code.T, x_sign.T, w_code, w_sign, kind="fused_fwd",
                       fmt=fmt, spec=spec, block_r=block_m, block_c=block_n,
                       block_ct=block_k,
                       interpret=resolve_interpret(interpret),
                       fwd_epilogue=epilogue, bias_code=bias_code,
                       bias_sign=bias_sign)


def lns_matmul_dw_update_pallas(x_code, x_sign, dy_code, dy_sign, *,
                                w_code, w_sign, epilogue: UpdateEpilogue,
                                fmt: LNSFormat, spec: DeltaSpec,
                                m_code=None, m_sign=None,
                                block_k: int = 128, block_n: int = 128,
                                block_m: int = 128,
                                interpret: Optional[bool] = None):
    """Backward-weight ⊞-MAC with the fused ⊞-SGD update at flush.

    Computes ``dW = Xᵀ ⊞-MAC dY`` and, at the final accumulator flush,
    applies the paper's log-domain SGD (⊞-momentum + weight decay, per
    ``epilogue``) against the resident ``w``/``m`` tiles: the outputs are
    the *updated* weight codes (+ updated momentum planes when the
    epilogue has momentum) — the gradient never round-trips through
    memory.  Bit-exact against ``matmul_dw`` + ``apply_update_codes``.
    """
    return _launch_mac(x_code, x_sign, dy_code, dy_sign, kind="dw_update",
                       fmt=fmt, spec=spec, block_r=block_k, block_c=block_n,
                       block_ct=block_m,
                       interpret=resolve_interpret(interpret),
                       update_epilogue=epilogue, w_code=w_code,
                       w_sign=w_sign, m_code=m_code, m_sign=m_sign)
