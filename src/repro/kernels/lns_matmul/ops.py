"""Jit'd public wrappers around the LNS matmul Pallas kernels, plus the
differentiable ``lns_matmul_trainable`` op.

``lns_matmul_trainable`` is the custom_vjp boundary between JAX autodiff and
the log-domain arithmetic: the primal and both cotangent matmuls run the
⊞-MAC path (emulated or Pallas, per :class:`~repro.core.lns.LNSMatmulBackend`),
so ``jax.grad`` through a model using it trains on the same hardware-shaped
datapath as the paper's hand backprop.  The entry points take the backend
their caller resolved (``LNSRuntime.matmul``) or its pieces, never a spec.
"""
from __future__ import annotations

from functools import partial

import jax

from ...core.delta import DeltaSpec
from ...core.formats import LNSFormat
from ...core.lns import LNSArray, LNSMatmulBackend, decode, encode
from ...core.sgd import UpdateEpilogue
from .grouped import lns_gmm_dw_pallas, lns_gmm_dx_pallas, lns_gmm_pallas
from .lns_matmul import (FwdEpilogue, lns_matmul_dw_pallas,
                         lns_matmul_dw_partials_pallas,
                         lns_matmul_dw_update_pallas, lns_matmul_dx_pallas,
                         lns_matmul_fused_pallas, lns_matmul_pallas)
from .ref import lns_gmm_dw_ref, lns_gmm_dx_ref, lns_gmm_ref
from .update import lns_fused_update_pallas


@partial(jax.jit, static_argnames=("kind", "fmt", "spec", "block_r",
                                   "block_c", "block_ct", "interpret"))
def _call(kind, a_code, a_sign, b_code, b_sign, fmt, spec,
          block_r, block_c, block_ct, interpret):
    fn = {"fwd": lns_matmul_pallas,
          "dx": lns_matmul_dx_pallas,
          "dw": lns_matmul_dw_pallas}[kind]
    kw = {"fwd": dict(block_m=block_r, block_n=block_c, block_k=block_ct),
          "dx": dict(block_m=block_r, block_k=block_c, block_n=block_ct),
          "dw": dict(block_k=block_r, block_n=block_c, block_m=block_ct),
          }[kind]
    return fn(a_code, a_sign.astype("int32"), b_code,
              b_sign.astype("int32"), fmt=fmt, spec=spec,
              interpret=interpret, **kw)


def lns_matmul_kernel(x: LNSArray, w: LNSArray, *, fmt: LNSFormat,
                      spec: DeltaSpec, block_m: int = 128,
                      block_n: int = 128, block_k: int = 128,
                      interpret: bool | None = None) -> LNSArray:
    """(M, K) ⊞-MAC (K, N) → (M, N) via the Pallas kernel.

    ``interpret=None`` compiles the kernel on a TPU and runs the Pallas
    interpreter on any other platform (``core.lns.resolve_interpret``);
    ``True`` / ``False`` force either.
    """
    code, sign = _call("fwd", x.code, x.sign, w.code, w.sign, fmt, spec,
                       block_m, block_n, block_k, interpret)
    return LNSArray(code, sign.astype("int8"))


def lns_matmul_dx_kernel(dy: LNSArray, w: LNSArray, *, fmt: LNSFormat,
                         spec: DeltaSpec, block_m: int = 128,
                         block_k: int = 128, block_n: int = 128,
                         interpret: bool | None = None) -> LNSArray:
    """Backward-activation kernel: dY (M, N) ⊞-MAC Wᵀ → dX (M, K)."""
    code, sign = _call("dx", dy.code, dy.sign, w.code, w.sign, fmt, spec,
                       block_m, block_k, block_n, interpret)
    return LNSArray(code, sign.astype("int8"))


def lns_matmul_dw_kernel(x: LNSArray, dy: LNSArray, *, fmt: LNSFormat,
                         spec: DeltaSpec, block_k: int = 128,
                         block_n: int = 128, block_m: int = 128,
                         interpret: bool | None = None) -> LNSArray:
    """Backward-weight kernel: Xᵀ ⊞-MAC dY (M, N) → dW (K, N)."""
    code, sign = _call("dw", x.code, x.sign, dy.code, dy.sign, fmt, spec,
                       block_k, block_n, block_m, interpret)
    return LNSArray(code, sign.astype("int8"))


@partial(jax.jit, static_argnames=("num_segments", "fmt", "spec", "block_k",
                                   "block_n", "interpret"))
def _call_dw_partials(x_code, x_sign, dy_code, dy_sign, num_segments, fmt,
                      spec, block_k, block_n, interpret):
    return lns_matmul_dw_partials_pallas(
        x_code, x_sign.astype("int32"), dy_code, dy_sign.astype("int32"),
        num_segments=num_segments, fmt=fmt, spec=spec, block_k=block_k,
        block_n=block_n, interpret=interpret)


def lns_matmul_dw_partials_kernel(x: LNSArray, dy: LNSArray, *,
                                  num_segments: int, fmt: LNSFormat,
                                  spec: DeltaSpec, block_k: int = 128,
                                  block_n: int = 128,
                                  interpret: bool | None = None) -> LNSArray:
    """Segmented backward-weight kernel: (S, K, N) per-segment dW partials.

    The batch M is cut into ``num_segments`` contiguous equal segments; slot
    ``s`` holds the sequential ⊞-MAC over segment ``s``'s rows only.  The
    deterministic data-parallel all-reduce (``distributed/lns_reduce.py``)
    ⊞-combines these slots in canonical segment order.
    """
    code, sign = _call_dw_partials(x.code, x.sign, dy.code, dy.sign,
                                   num_segments, fmt, spec, block_k, block_n,
                                   interpret)
    return LNSArray(code, sign.astype("int8"))


# ------------------------------------------------------------------------
# Fused-epilogue entry points (flush-time bias/llrelu/requantize + ⊞-SGD)
# ------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("fmt", "spec", "epilogue", "block_m",
                                   "block_n", "block_k", "interpret"))
def _call_fused_fwd(x_code, x_sign, w_code, w_sign, bias_code, bias_sign,
                    fmt, spec, epilogue, block_m, block_n, block_k,
                    interpret):
    return lns_matmul_fused_pallas(
        x_code, x_sign.astype("int32"), w_code, w_sign.astype("int32"),
        fmt=fmt, spec=spec, epilogue=epilogue, bias_code=bias_code,
        bias_sign=(None if bias_sign is None else bias_sign.astype("int32")),
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret)


def lns_matmul_fused_kernel(x: LNSArray, w: LNSArray, *,
                            epilogue: FwdEpilogue,
                            bias: "LNSArray | None" = None,
                            fmt: LNSFormat, spec: DeltaSpec,
                            block_m: int = 128, block_n: int = 128,
                            block_k: int = 128,
                            interpret: bool | None = None):
    """Forward ⊞-MAC with the flush-time epilogue — one kernel pass.

    Returns the epilogued product (in ``epilogue.dst_fmt`` when set), or
    ``(z, z_sign)`` with the post-bias pre-activation sign plane when
    ``epilogue.emit_z_sign`` (what ``llrelu_grad`` needs in backward).
    """
    if epilogue.bias != (bias is not None):
        raise ValueError(
            f"epilogue.bias={epilogue.bias} but bias "
            f"{'was' if bias is not None else 'was not'} passed")
    outs = _call_fused_fwd(
        x.code, x.sign, w.code, w.sign,
        None if bias is None else bias.code,
        None if bias is None else bias.sign,
        fmt, spec, epilogue, block_m, block_n, block_k, interpret)
    z = LNSArray(outs[0], outs[1].astype("int8"))
    if epilogue.emit_z_sign:
        return z, outs[2].astype("int8")
    return z


@partial(jax.jit, static_argnames=("fmt", "spec", "epilogue", "block_k",
                                   "block_n", "block_m", "interpret"))
def _call_dw_update(x_code, x_sign, dy_code, dy_sign, w_code, w_sign,
                    m_code, m_sign, fmt, spec, epilogue, block_k, block_n,
                    block_m, interpret):
    return lns_matmul_dw_update_pallas(
        x_code, x_sign.astype("int32"), dy_code, dy_sign.astype("int32"),
        w_code=w_code, w_sign=w_sign.astype("int32"),
        m_code=m_code,
        m_sign=(None if m_sign is None else m_sign.astype("int32")),
        epilogue=epilogue, fmt=fmt, spec=spec, block_k=block_k,
        block_n=block_n, block_m=block_m, interpret=interpret)


def lns_matmul_dw_update_kernel(x: LNSArray, dy: LNSArray, *, w: LNSArray,
                                epilogue: UpdateEpilogue,
                                fmt: LNSFormat, spec: DeltaSpec,
                                m: "LNSArray | None" = None,
                                block_k: int = 128, block_n: int = 128,
                                block_m: int = 128,
                                interpret: bool | None = None):
    """Backward-weight ⊞-MAC with the ⊞-SGD update fused into the flush.

    ``dW = Xᵀ ⊞-MAC dY`` never leaves VMEM: the final accumulator is
    consumed by the update against the resident ``w``/``m`` tiles.
    Returns ``(w_new, m_new)`` (``m_new is None`` when the epilogue has no
    momentum).  Bit-exact against ``lns_matmul_dw_kernel`` +
    ``core.sgd.apply_update_codes``.
    """
    if epilogue.has_momentum != (m is not None):
        raise ValueError(
            f"epilogue momentum={epilogue.momentum_code} but momentum "
            f"state {'was' if m is not None else 'was not'} passed")
    outs = _call_dw_update(
        x.code, x.sign, dy.code, dy.sign, w.code, w.sign,
        None if m is None else m.code, None if m is None else m.sign,
        fmt, spec, epilogue, block_k, block_n, block_m, interpret)
    w_new = LNSArray(outs[0], outs[1].astype("int8"))
    if epilogue.has_momentum:
        return w_new, LNSArray(outs[2], outs[3].astype("int8"))
    return w_new, None


@partial(jax.jit, static_argnames=("fmt", "spec", "epilogue", "block",
                                   "interpret"))
def _call_fused_update(w_code, w_sign, g_code, g_sign, m_code, m_sign,
                       fmt, spec, epilogue, block, interpret):
    return lns_fused_update_pallas(
        w_code, w_sign.astype("int32"), g_code, g_sign.astype("int32"),
        m_code=m_code,
        m_sign=(None if m_sign is None else m_sign.astype("int32")),
        epilogue=epilogue, fmt=fmt, spec=spec, block=block,
        interpret=interpret)


def lns_fused_update_kernel(w: LNSArray, g: LNSArray, *,
                            epilogue: UpdateEpilogue, fmt: LNSFormat,
                            spec: DeltaSpec, m: "LNSArray | None" = None,
                            block: int = 8192,
                            interpret: bool | None = None):
    """One-pass fused ⊞-SGD update: ``(w, m, g) → (w', m')``.

    The post-⊞-combine epilogue of the DP deterministic reduce (reused by
    ``distributed/lns_dp.py``) and the bias-update path of the fused
    train step.  Returns ``(w_new, m_new)`` (``m_new is None`` without
    momentum).
    """
    if epilogue.has_momentum != (m is not None):
        raise ValueError(
            f"epilogue momentum={epilogue.momentum_code} but momentum "
            f"state {'was' if m is not None else 'was not'} passed")
    outs = _call_fused_update(
        w.code, w.sign, g.code, g.sign,
        None if m is None else m.code, None if m is None else m.sign,
        fmt, spec, epilogue, block, interpret)
    w_new = LNSArray(outs[0], outs[1].astype("int8"))
    if epilogue.has_momentum:
        return w_new, LNSArray(outs[2], outs[3].astype("int8"))
    return w_new, None


# ------------------------------------------------------------------------
# Differentiable op: LNS forward AND backward under jax.grad
# ------------------------------------------------------------------------
@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _trainable(x, w, be: LNSMatmulBackend):
    z = be.matmul(encode(x, be.fmt), encode(w, be.fmt))
    return decode(z, be.fmt)


def _trainable_fwd(x, w, be):
    xq, wq = encode(x, be.fmt), encode(w, be.fmt)
    z = be.matmul(xq, wq)
    # Residuals are the already-encoded operands: the backward ⊞-MACs
    # consume LNS codes directly, so re-encoding would be pure waste.
    return decode(z, be.fmt), (xq, wq)


def _trainable_bwd(be, res, g):
    xq, wq = res
    f = be.fmt
    dy = encode(g, f)
    dx = be.matmul_dx(dy, wq)
    dw = be.matmul_dw(xq, dy)
    return decode(dx, f), decode(dw, f)


_trainable.defvjp(_trainable_fwd, _trainable_bwd)


def lns_matmul_trainable(x, w, be: LNSMatmulBackend):
    """Differentiable float-view matmul on the log-domain MAC path.

    ``x``: (..., K) float, ``w``: (K, N) float.  Forward encodes both
    operands to LNS, runs the ⊞-MAC matmul on ``be``, and decodes; the
    VJP encodes the cotangent and runs the *transposed* ⊞-MACs
    (dX = dY ⊞ Wᵀ, dW = Xᵀ ⊞ dY) on the same path — no float matmul in
    either direction.  Every later scaling PR (sharded training, batched
    serving on the kernel path) composes with this boundary.

    ``be`` is the backend a numerics spec resolves to, e.g.
    ``plan.runtime_for("hidden").matmul``, or one built from the pieces
    (``LNSMatmulBackend(fmt=..., spec=..., backend="pallas")``).
    """
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    z = _trainable(x2, w, be)
    return z.reshape(lead + (w.shape[-1],))


# ------------------------------------------------------------------------
# Grouped ⊞-MAC (rows sorted by expert against per-expert weights)
# ------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("kind", "fmt", "spec", "block_rows",
                                   "block_n", "block_k", "interpret"))
def _gmm_call(kind, a_code, a_sign, b_code, b_sign, sizes, fmt, spec,
              block_rows, block_n, block_k, interpret):
    fn = {"fwd": lns_gmm_pallas, "dx": lns_gmm_dx_pallas,
          "dw": lns_gmm_dw_pallas}[kind]
    return fn(a_code, a_sign.astype("int32"), b_code,
              b_sign.astype("int32"), sizes, fmt=fmt, spec=spec,
              block_rows=block_rows, block_n=block_n, block_k=block_k,
              interpret=interpret)


def _gmm(be: LNSMatmulBackend, kind: str, a: LNSArray, b: LNSArray, sizes):
    """One grouped product on the backend's path: the kernels, or the
    per-group emulated ⊞-MACs they are bit-identical to."""
    if be.backend == "pallas":
        code, sign = _gmm_call(kind, a.code, a.sign, b.code, b.sign, sizes,
                               be.fmt, be.spec, be.block_m, be.block_n,
                               be.block_k, be._interp())
    else:
        fn = {"fwd": lns_gmm_ref, "dx": lns_gmm_dx_ref,
              "dw": lns_gmm_dw_ref}[kind]
        code, sign = fn(a.code, a.sign.astype("int32"), b.code,
                        b.sign.astype("int32"), sizes, fmt=be.fmt,
                        spec=be.spec)
    return LNSArray(code, sign.astype("int8"))


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(x, w, sizes, be: LNSMatmulBackend):
    return decode(_gmm(be, "fwd", encode(x, be.fmt), encode(w, be.fmt),
                       sizes), be.fmt)


def _grouped_fwd(x, w, sizes, be):
    xq, wq = encode(x, be.fmt), encode(w, be.fmt)
    return decode(_gmm(be, "fwd", xq, wq, sizes), be.fmt), (xq, wq, sizes)


def _grouped_bwd(be, res, g):
    xq, wq, sizes = res
    dy = encode(g, be.fmt)
    return (decode(_gmm(be, "dx", dy, wq, sizes), be.fmt),
            decode(_gmm(be, "dw", xq, dy, sizes), be.fmt), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def lns_gmm_trainable(x, w, sizes, be: LNSMatmulBackend):
    """Differentiable grouped ⊞-MAC: ``x`` (M, K) float rows sorted by
    group, ``w`` (G, K, N) float, ``sizes`` (G,) int32 rows per group.

    Row ``r`` of group ``g`` gets ``x[r] ⊞-MAC w[g]``; rows past
    ``sum(sizes)`` get 0.  As :func:`lns_matmul_trainable`, the forward
    and both cotangent products (dX per row against ``w[g]ᵀ``, dW per
    group over its rows in the order given) run on ``be``, here the
    grouped kernels (``grouped.py``); ``be.block_m`` is the row tile.
    """
    return _grouped(x, w, sizes.astype("int32"), be)
