"""Pure-jnp oracles for the LNS matmul Pallas kernels (forward + backward).

The kernels accumulate sequentially over the *entire* contraction dimension
(the innermost grid axis revisits the output tile, and the in-tile fori_loop
walks the contraction ascending), so every oracle is
``core.arithmetic.lns_matmul`` with ``order="sequential"`` on suitably
transposed operands — the comparison is **bit-exact**, not approximate.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...core.activations import llrelu
from ...core.arithmetic import bias_add, lns_matmul
from ...core.delta import DeltaEngine, DeltaSpec
from ...core.formats import LNSFormat
from ...core.lns import LNSArray, convert_format
from ...core.sgd import UpdateEpilogue, apply_update_codes


def _mm(a_code, a_sign, b_code, b_sign, fmt, spec, *, t_a=False, t_b=False):
    eng = DeltaEngine(spec, fmt)
    a = LNSArray(a_code, a_sign.astype("int8"))
    b = LNSArray(b_code, b_sign.astype("int8"))
    if t_a:
        a = a.T
    if t_b:
        b = b.T
    z = lns_matmul(a, b, eng, order="sequential")
    return z.code, z.sign.astype("int32")


def lns_matmul_ref(x_code, x_sign, w_code, w_sign, *, fmt: LNSFormat,
                   spec: DeltaSpec):
    """Forward oracle: Z = X ⊞-MAC W, sequential over K."""
    return _mm(x_code, x_sign, w_code, w_sign, fmt, spec)


def lns_matmul_dx_ref(dy_code, dy_sign, w_code, w_sign, *, fmt: LNSFormat,
                      spec: DeltaSpec):
    """Backward-activation oracle: dX = dY ⊞-MAC Wᵀ, sequential over N."""
    return _mm(dy_code, dy_sign, w_code, w_sign, fmt, spec, t_b=True)


def lns_matmul_dw_ref(x_code, x_sign, dy_code, dy_sign, *, fmt: LNSFormat,
                      spec: DeltaSpec):
    """Backward-weight oracle: dW = Xᵀ ⊞-MAC dY, sequential over M."""
    return _mm(x_code, x_sign, dy_code, dy_sign, fmt, spec, t_a=True)


def lns_matmul_dw_partials_ref(x_code, x_sign, dy_code, dy_sign, *,
                               num_segments: int, fmt: LNSFormat,
                               spec: DeltaSpec):
    """Per-segment dW oracle: out[s] = X[seg_s]ᵀ ⊞-MAC dY[seg_s].

    The batch M is cut into ``num_segments`` contiguous equal segments;
    each partial is the sequential-order dW over its segment's rows only
    (bit-exact vs ``lns_matmul_dw_partials_pallas``).
    """
    m = x_code.shape[0]
    assert m % num_segments == 0, (m, num_segments)
    seg = m // num_segments
    codes, signs = [], []
    for s in range(num_segments):
        sl = slice(s * seg, (s + 1) * seg)
        c, sg = _mm(x_code[sl], x_sign[sl], dy_code[sl], dy_sign[sl],
                    fmt, spec, t_a=True)
        codes.append(c)
        signs.append(sg)
    return jnp.stack(codes), jnp.stack(signs)


def lns_matmul_fused_ref(x_code, x_sign, w_code, w_sign, *,
                         fmt: LNSFormat, spec: DeltaSpec, epilogue,
                         bias_code=None, bias_sign=None):
    """Fused-forward oracle: the *unfused composition* the kernel folds in.

    Sequential ⊞-MAC, then — as separate ops, exactly what the pre-fusion
    train step ran — ``bias_add``, ``llrelu``, ``convert_format``, per the
    :class:`~repro.kernels.lns_matmul.lns_matmul.FwdEpilogue`.  Returns
    ``(code, sign, z_sign)`` with ``z_sign`` the post-bias pre-activation
    sign plane; comparisons against the fused kernel are **bit-exact**.
    """
    eng = DeltaEngine(spec, fmt)
    z = lns_matmul(LNSArray(x_code, x_sign.astype("int8")),
                   LNSArray(w_code, w_sign.astype("int8")), eng,
                   order="sequential")
    if epilogue.bias:
        z = bias_add(z, LNSArray(bias_code, bias_sign.astype("int8")), eng)
    z_sign = z.sign
    if epilogue.llrelu_beta is not None:
        z = llrelu(z, epilogue.llrelu_beta, fmt)
    if epilogue.dst_fmt is not None:
        z = convert_format(z, fmt, epilogue.dst_fmt)
    return z.code, z.sign.astype("int32"), z_sign.astype("int32")


def lns_matmul_dw_update_ref(x_code, x_sign, dy_code, dy_sign, *,
                             w: LNSArray, epilogue: UpdateEpilogue,
                             fmt: LNSFormat, spec: DeltaSpec,
                             m: "LNSArray | None" = None):
    """Fused dW-update oracle: sequential dW, then the unfused ⊞-SGD.

    ``matmul_dw`` followed by :func:`~repro.core.sgd.apply_update_codes`
    — the exact composition the fused kernel's flush replaces.  Returns
    ``(w_new, m_new)`` LNSArrays; bit-exact against
    ``lns_matmul_dw_update_kernel``.
    """
    gc, gs = _mm(x_code, x_sign, dy_code, dy_sign, fmt, spec, t_a=True)
    eng = DeltaEngine(spec, fmt)
    return apply_update_codes(w, LNSArray(gc, gs.astype("int8")), m,
                              epilogue, eng)


def _group_masks(sizes, m: int):
    """(G, M) bool: row r belongs to group g (rows sorted by group; rows
    past ``sum(sizes)`` to none)."""
    end = jnp.cumsum(sizes)
    r = jnp.arange(m)
    return (r[None, :] >= (end - sizes)[:, None]) & (r[None, :] < end[:, None])


def _mask_rows(code, sign, mask, fmt):
    return (jnp.where(mask[:, None], code, fmt.zero_code),
            jnp.where(mask[:, None], sign, 0))


def _gmm_rows(a_code, a_sign, w_code, w_sign, sizes, fmt, spec, t_b):
    masks = _group_masks(sizes, a_code.shape[0])
    out_c = out_s = None
    for g in range(w_code.shape[0]):
        c, s = _mm(*_mask_rows(a_code, a_sign, masks[g], fmt), w_code[g],
                   w_sign[g], fmt, spec, t_b=t_b)
        if out_c is None:
            out_c, out_s = (jnp.full_like(c, fmt.zero_code),
                            jnp.zeros_like(s))
        out_c = jnp.where(masks[g][:, None], c, out_c)
        out_s = jnp.where(masks[g][:, None], s, out_s)
    return out_c, out_s


def lns_gmm_ref(x_code, x_sign, w_code, w_sign, sizes, *, fmt: LNSFormat,
                spec: DeltaSpec):
    """Grouped forward oracle: each group's rows ⊞-MAC its weights."""
    return _gmm_rows(x_code, x_sign, w_code, w_sign, sizes, fmt, spec, False)


def lns_gmm_dx_ref(dy_code, dy_sign, w_code, w_sign, sizes, *,
                   fmt: LNSFormat, spec: DeltaSpec):
    """Grouped dX oracle: each group's rows ⊞-MAC its weights ᵀ."""
    return _gmm_rows(dy_code, dy_sign, w_code, w_sign, sizes, fmt, spec,
                     True)


def lns_gmm_dw_ref(x_code, x_sign, dy_code, dy_sign, sizes, *,
                   fmt: LNSFormat, spec: DeltaSpec):
    """Grouped dW oracle: X_gᵀ ⊞-MAC dY_g over group g's rows in order
    (other rows are the zero code, the ⊞ identity)."""
    masks = _group_masks(sizes, x_code.shape[0])
    outs = [_mm(*_mask_rows(x_code, x_sign, masks[g], fmt), dy_code,
                dy_sign, fmt, spec, t_a=True) for g in range(sizes.shape[0])]
    return (jnp.stack([c for c, _ in outs]), jnp.stack([s for _, s in outs]))
