"""LNS tensor type, float <-> LNS codecs, and the matmul backend dispatcher.

An :class:`LNSArray` carries two integer arrays of identical shape:

* ``code``: int32, fixed-point encoding of ``X = log2|v|`` (``qf`` fraction
  bits), with ``fmt.zero_code`` as the reserved exact-zero sentinel;
* ``sign``: int8, **1 = negative**, 0 = positive.  (The paper uses
  ``s=1 ⇔ v>0``; this is a pure convention flip, the XOR algebra is
  identical.  All tests are roundtrip-based.)

It is registered as a pytree so it flows through jit/scan/vmap untouched.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as _obs
from .formats import LNSFormat


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LNSArray:
    code: jax.Array  # int32
    sign: jax.Array  # int8, 1 = negative

    def tree_flatten(self):
        return (self.code, self.sign), None

    @classmethod
    def tree_unflatten(cls, aux: Any, children):
        return cls(*children)

    @property
    def shape(self):
        return self.code.shape

    @property
    def ndim(self):
        return self.code.ndim

    def __getitem__(self, idx):
        return LNSArray(self.code[idx], self.sign[idx])

    def reshape(self, *shape):
        return LNSArray(self.code.reshape(*shape), self.sign.reshape(*shape))

    def transpose(self, *axes):
        axes = axes or None
        return LNSArray(self.code.transpose(*axes) if axes else self.code.T,
                        self.sign.transpose(*axes) if axes else self.sign.T)

    @property
    def T(self):
        return LNSArray(self.code.T, self.sign.T)


def encode(v: jax.Array, fmt: LNSFormat) -> LNSArray:
    """Quantize a float array into LNS fixed point (paper eq. 1).

    Zeros (and magnitudes underflowing the format) map to the reserved
    ``zero_code``; magnitudes overflowing saturate to ``code_max``.
    """
    v = jnp.asarray(v, jnp.float32)
    mag = jnp.abs(v)
    # Avoid log2(0): the zero lanes are overwritten below.
    safe = jnp.where(mag > 0, mag, 1.0)
    x = jnp.log2(safe)
    raw = jnp.round(x * fmt.scale)
    code = raw.astype(jnp.int32)
    if _obs.scope_active():
        # Pre-clip quantization health (pure reads; results unchanged).
        _obs.observe_quantize(code, mag > 0, fmt)
    code = jnp.clip(code, fmt.min_nonzero_code, fmt.code_max)
    code = jnp.where(mag > 0, code, np.int32(fmt.zero_code))
    # Flush-to-zero for true underflow (rounded below representable range).
    underflow = raw < fmt.min_nonzero_code
    code = jnp.where((mag > 0) & underflow, np.int32(fmt.zero_code), code)
    sign = (v < 0).astype(jnp.int8)
    return LNSArray(code, sign)


def decode(a: LNSArray, fmt: LNSFormat) -> jax.Array:
    """Map LNS codes back to float32: v = ±2^(code / 2^qf)."""
    x = a.code.astype(jnp.float32) / fmt.scale
    mag = jnp.exp2(x)
    mag = jnp.where(a.code == fmt.zero_code, 0.0, mag)
    s = jnp.where(a.sign == 1, -1.0, 1.0)
    return s * mag


def zeros(shape, fmt: LNSFormat) -> LNSArray:
    return LNSArray(
        jnp.full(shape, fmt.zero_code, jnp.int32),
        jnp.zeros(shape, jnp.int8),
    )


def from_parts(code, sign) -> LNSArray:
    return LNSArray(jnp.asarray(code, jnp.int32), jnp.asarray(sign, jnp.int8))


def scalar(v: float, fmt: LNSFormat) -> LNSArray:
    """Host-side scalar constant in LNS (e.g. learning rate, log2(e))."""
    if v == 0:
        return LNSArray(jnp.int32(fmt.zero_code), jnp.int8(0))
    code = fmt.to_code(float(np.log2(abs(v))))
    return LNSArray(jnp.int32(code), jnp.int8(1 if v < 0 else 0))


def convert_format(a: LNSArray, src: LNSFormat, dst: LNSFormat) -> LNSArray:
    """Re-encode LNS codes between formats by pure integer shifts.

    The log-magnitude is format-independent; only the fixed-point grid
    changes, so ``code_dst = round(code_src · 2^(qf_dst - qf_src))`` — a
    left shift when widening (exact, e.g. lns12 → lns16), an add-half +
    arithmetic right shift (round-half-up) when narrowing.  This is the
    barrel-shifter a mixed-format accelerator puts between layers of
    different bitwidths; no float round-trip, so widening is lossless.
    Zero sentinels are preserved, out-of-range magnitudes saturate, and
    magnitudes below the destination's resolution flush to zero.
    """
    if src == dst:
        return a
    shift = dst.qf - src.qf
    if shift >= 0:
        code = a.code << shift
    else:
        half = 1 << (-shift - 1)
        code = (a.code + half) >> (-shift)
    if _obs.scope_active():
        # Pre-clip crossing health against the destination grid.
        _obs.observe_convert(a.code != src.zero_code, code, dst)
    underflow = code < dst.min_nonzero_code
    code = jnp.clip(code, dst.min_nonzero_code, dst.code_max)
    zero = (a.code == src.zero_code) | underflow
    code = jnp.where(zero, np.int32(dst.zero_code), code)
    return LNSArray(code.astype(jnp.int32),
                    jnp.where(zero, jnp.int8(0), a.sign))


def quantization_bound(fmt: LNSFormat) -> float:
    """Max relative error of encode/decode for in-range values.

    |v̂ - v| / |v| <= 2^(2^-(qf+1)) - 1  (half-ulp of the log code).
    """
    return float(2.0 ** (0.5 / fmt.scale) - 1.0)


# ------------------------------------------------------------------------
# Matmul backend dispatcher
# ------------------------------------------------------------------------

#: The valid values of every ``matmul_backend`` / ``backend`` switch in the
#: repo (``LNSMatmulBackend``, ``MLPConfig``, ``TrainConfig``,
#: ``NumericsPolicy``).  ``"emulate"`` is the pure-jnp sequential ⊞-MAC,
#: ``"pallas"`` the blocked TPU kernels — bit-exact to each other.
MATMUL_BACKENDS = ("emulate", "pallas")


def resolve_interpret(interpret: "bool | None") -> bool:
    """Whether a Pallas kernel launch runs in interpret mode.

    The one place the choice is made: an explicit flag wins, and ``None``
    means compiled on a TPU and interpreted on any other platform — so no
    launch falls back to the interpreter on the chip unless asked to.
    """
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"

# Engine cache keyed by the full (DeltaSpec, LNSFormat) pair — both are
# frozen/hashable dataclasses.  The key must include the *format*: the same
# Δ spec yields different integer tables under lns16 (qf=10) and lns12
# (qf=6), so a name- or spec-only key would alias engines across formats.
_ENGINE_CACHE: dict = {}


def _cached_engine(spec, fmt: LNSFormat):
    key = (spec, fmt)
    if key not in _ENGINE_CACHE:
        from .delta import DeltaEngine
        _ENGINE_CACHE[key] = DeltaEngine(spec, fmt)
    return _ENGINE_CACHE[key]


#: The ``blocks`` axis: "default" (caller-/runtime-chosen tile sizes),
#: "auto" (per-(spec, op, shape) autotuner — kernels/autotune.py), or an
#: explicit "MxNxK" (block_m × block_n × block_k).
BLOCK_MODES = ("default", "auto", "<M>x<N>x<K>")


def parse_blocks(text: str):
    """Decode an explicit ``MxNxK`` blocks value → (block_m, block_n,
    block_k); raises with the valid forms for anything else."""
    parts = text.split("x")
    if len(parts) == 3:
        try:
            bm, bn, bk = (int(p) for p in parts)
            if bm > 0 and bn > 0 and bk > 0:
                return bm, bn, bk
        except ValueError:
            pass
    raise ValueError(f"invalid blocks={text!r}; valid values: "
                     f"{', '.join(BLOCK_MODES)}")


def resolve_blocks_arg(blocks: str, block_m: int, block_n: int,
                       block_k: int):
    """Fold a spec's ``blocks`` axis onto caller-supplied tile sizes.

    Returns ``(block_m, block_n, block_k, mode)`` where ``mode`` is what
    :class:`LNSMatmulBackend` stores: ``"auto"`` defers to the autotuner
    per op+shape at launch; an explicit ``MxNxK`` overrides the caller's
    sizes and ``"default"`` keeps them.  The one decode point shared by
    ``LNSRuntime``, the ⊞-reduce kernel's entry point and the
    data-parallel reduce.
    """
    if blocks == "auto":
        return block_m, block_n, block_k, "auto"
    if blocks != "default":
        bm, bn, bk = parse_blocks(blocks)
        return bm, bn, bk, "default"
    return block_m, block_n, block_k, "default"


@dataclasses.dataclass(frozen=True)
class LNSMatmulBackend:
    """Config-selected implementation of the ⊞-MAC matmul + its backward.

    Callers pick the execution path by configuration instead of by import:

    * ``backend="emulate"`` — pure-jnp emulation (``core.arithmetic``) with
      ``order="sequential"``, the paper's scalar MAC pipeline;
    * ``backend="pallas"``  — the blocked Pallas kernels
      (``kernels/lns_matmul``), which reproduce the same sequential MAC
      ordering **bit-exactly**, so the two backends are interchangeable down
      to the last weight code.

    All three products of the training step are covered (eqs. 10-14), plus
    the segmented variant that feeds the data-parallel gradient reduction:

    * ``matmul(x, w)``     Z  = X ⊞-MAC W          (forward)
    * ``matmul_dx(dy, w)`` dX = dY ⊞-MAC Wᵀ       (backward, activations)
    * ``matmul_dw(x, dy)`` dW = Xᵀ ⊞-MAC dY       (backward, weights)
    * ``matmul_dw_partials(x, dy, S)``  per-segment dW partial codes
      (S, K, N) — the emission side of the deterministic ⊞-allreduce
      (``distributed/lns_reduce.py``)

    ``interpret=None`` (the default) resolves *at call time*, not at
    construction: interpret mode switches on automatically whenever the
    attached jax backend is not a real TPU, so the same config object runs
    the compiled kernels on TPU and the Pallas interpreter on CPU.  Emulated
    Δ engines are shared via a cache keyed by the full ``(spec, fmt)`` pair
    (see ``_cached_engine``).  The dataclass is frozen/hashable so it can be
    closed over by jit or passed as a static argument.
    """

    fmt: LNSFormat
    spec: Any  # DeltaSpec
    backend: str = "emulate"          # one of MATMUL_BACKENDS
    block_m: int = 128
    block_n: int = 128
    block_k: int = 128
    interpret: bool | None = None
    blocks: str = "default"           # 'default' (fixed block_m/n/k) or
                                      # 'auto' (autotuned per op + shape)

    def __post_init__(self):
        if self.backend not in MATMUL_BACKENDS:
            raise ValueError(
                f"unknown matmul backend {self.backend!r}; "
                f"expected one of {MATMUL_BACKENDS}")
        if self.blocks not in ("default", "auto"):
            raise ValueError(
                f"unknown blocks mode {self.blocks!r}; expected 'default' "
                f"or 'auto' (explicit MxNxK strings are resolved by "
                f"resolve_blocks_arg before construction)")

    def _interp(self) -> bool:
        return resolve_interpret(self.interpret)

    def _op_blocks(self, op: str, r: int, c: int, ct: int):
        """Effective (block_r, block_c, block_ct) for one kernel launch.

        ``blocks='auto'`` consults the autotuner cache per (op, shape) —
        measured entries when a prior eager tune/prime filled them, the
        deterministic heuristic otherwise (block sizes never change
        results, only speed).  ``'default'`` keeps the fixed per-op
        mapping of this backend's block_m/n/k.
        """
        if self.blocks == "auto":
            from ..kernels import autotune
            return autotune.lookup(op, (r, c, ct), fmt=self.fmt,
                                   spec=self.spec,
                                   interpret=self._interp())
        return {"fwd": (self.block_m, self.block_n, self.block_k),
                "dx": (self.block_m, self.block_k, self.block_n),
                "dw": (self.block_k, self.block_n, self.block_m),
                "dw_partials": (self.block_k, self.block_n, 0)}[op]

    def matmul(self, x: "LNSArray", w: "LNSArray") -> "LNSArray":
        """Forward (M, K) ⊞-MAC (K, N) → (M, N), sequential over K."""
        if self.backend == "pallas":
            from ..kernels.lns_matmul import lns_matmul_kernel
            bm, bn, bk = self._op_blocks("fwd", x.shape[0], w.shape[1],
                                         x.shape[1])
            return lns_matmul_kernel(
                x, w, fmt=self.fmt, spec=self.spec, block_m=bm,
                block_n=bn, block_k=bk, interpret=self._interp())
        from .arithmetic import lns_matmul
        return lns_matmul(x, w, _cached_engine(self.spec, self.fmt),
                          order="sequential")

    def matmul_dx(self, dy: "LNSArray", w: "LNSArray") -> "LNSArray":
        """Backward dX = dY (M, N) ⊞-MAC Wᵀ (N, K), sequential over N."""
        if self.backend == "pallas":
            from ..kernels.lns_matmul import lns_matmul_dx_kernel
            bm, bk, bn = self._op_blocks("dx", dy.shape[0], w.shape[0],
                                         dy.shape[1])
            return lns_matmul_dx_kernel(
                dy, w, fmt=self.fmt, spec=self.spec, block_m=bm,
                block_k=bk, block_n=bn, interpret=self._interp())
        from .arithmetic import lns_matmul
        return lns_matmul(dy, w.T, _cached_engine(self.spec, self.fmt),
                          order="sequential")

    def matmul_dw(self, x: "LNSArray", dy: "LNSArray") -> "LNSArray":
        """Backward dW = Xᵀ (K, M) ⊞-MAC dY (M, N), sequential over M."""
        if self.backend == "pallas":
            from ..kernels.lns_matmul import lns_matmul_dw_kernel
            bk, bn, bm = self._op_blocks("dw", x.shape[1], dy.shape[1],
                                         x.shape[0])
            return lns_matmul_dw_kernel(
                x, dy, fmt=self.fmt, spec=self.spec, block_k=bk,
                block_n=bn, block_m=bm, interpret=self._interp())
        from .arithmetic import lns_matmul
        return lns_matmul(x.T, dy, _cached_engine(self.spec, self.fmt),
                          order="sequential")

    def matmul_dw_partials(self, x: "LNSArray", dy: "LNSArray",
                           num_segments: int) -> "LNSArray":
        """Segmented dW: (S, K, N) per-segment partial codes.

        The batch M is cut into ``num_segments`` contiguous equal segments;
        slot ``s`` is the sequential ⊞-MAC over segment ``s``'s rows only.
        ⊞-combining the slots in order 0..S-1 reproduces ``matmul_dw`` over
        the canonical segmentation independent of which device produced
        which slot — the determinism contract of the DP gradient reduce.
        """
        if self.backend == "pallas":
            from ..kernels.lns_matmul import lns_matmul_dw_partials_kernel
            bk, bn, _ = self._op_blocks(
                "dw_partials", x.shape[1], dy.shape[1],
                x.shape[0] // max(1, num_segments))
            return lns_matmul_dw_partials_kernel(
                x, dy, num_segments=num_segments, fmt=self.fmt,
                spec=self.spec, block_k=bk, block_n=bn,
                interpret=self._interp())
        from .arithmetic import lns_matmul
        m = x.shape[0]
        if num_segments < 1 or m % num_segments:
            raise ValueError(
                f"batch {m} not divisible into {num_segments} segments")
        seg = m // num_segments
        eng = _cached_engine(self.spec, self.fmt)
        outs = [lns_matmul(x[s * seg:(s + 1) * seg].T,
                           dy[s * seg:(s + 1) * seg], eng,
                           order="sequential")
                for s in range(num_segments)]
        return LNSArray(jnp.stack([o.code for o in outs]),
                        jnp.stack([o.sign for o in outs]))

    def affine(self, x: "LNSArray", w: "LNSArray", b: "LNSArray"
               ) -> "LNSArray":
        """z = x·W + b with the matmul on this backend's path."""
        from .arithmetic import bias_add
        return bias_add(self.matmul(x, w), b,
                        _cached_engine(self.spec, self.fmt))

    # -- fused epilogues ---------------------------------------------------
    # Contract (ROADMAP §Fused epilogues): the epilogue runs at the
    # kernel's accumulator flush and, under data parallelism, strictly
    # *after* the canonical ⊞-combine of segment partials — so every
    # fused path below is bit-identical to its unfused composition, on
    # both backends.

    def matmul_fused(self, x: "LNSArray", w: "LNSArray", *,
                     bias: "LNSArray | None" = None,
                     llrelu_beta: "int | None" = None,
                     out_fmt: "LNSFormat | None" = None,
                     emit_z_sign: bool = False):
        """Forward ⊞-MAC with the flush-time epilogue, one pass.

        Optional pieces, applied in order at accumulator flush: bias ⊞,
        log-leaky-ReLU (``llrelu_beta``), and a requantize onto
        ``out_fmt``'s code grid (a layer crossing a NumericsPlan format
        boundary emits codes already in the target format).  Returns the
        epilogued product, or ``(z, z_sign)`` with the post-bias
        pre-activation sign plane when ``emit_z_sign`` (what
        ``llrelu_grad`` consumes in backward).  On ``backend="emulate"``
        this *is* the unfused composition; the Pallas kernel is
        bit-exact against it.
        """
        if out_fmt is not None and out_fmt == self.fmt:
            out_fmt = None
        if self.backend == "pallas":
            from ..kernels.lns_matmul import (FwdEpilogue,
                                              lns_matmul_fused_kernel)
            ep = FwdEpilogue(bias=bias is not None, llrelu_beta=llrelu_beta,
                             dst_fmt=out_fmt, emit_z_sign=emit_z_sign)
            bm, bn, bk = self._op_blocks("fwd", x.shape[0], w.shape[1],
                                         x.shape[1])
            out = lns_matmul_fused_kernel(
                x, w, epilogue=ep, bias=bias, fmt=self.fmt, spec=self.spec,
                block_m=bm, block_n=bn, block_k=bk,
                interpret=self._interp())
        else:
            from .activations import llrelu
            from .arithmetic import bias_add
            eng = _cached_engine(self.spec, self.fmt)
            # Suspend inner taps (the convert_format inside this
            # composition would tap on emulate but not inside the Pallas
            # kernel): both backends emit exactly the dispatch-level
            # epi_fwd tap below, so label sets are backend-identical.
            with _obs.suspended():
                z = self.matmul(x, w)
                if bias is not None:
                    z = bias_add(z, bias, eng)
                z_sign = z.sign
                if llrelu_beta is not None:
                    z = llrelu(z, llrelu_beta, self.fmt)
                if out_fmt is not None:
                    z = convert_format(z, self.fmt, out_fmt)
            out = (z, z_sign) if emit_z_sign else z
        if _obs.scope_active():
            # Flush hook: epilogued output health, identical labels on
            # both backends (the tap lives at the dispatch level, outside
            # the kernel's custom_vjp/jit internals).
            _obs.observe_codes(out[0] if emit_z_sign else out,
                               out_fmt if out_fmt is not None else self.fmt,
                               op="epi_fwd")
        return out

    def matmul_dw_update(self, x: "LNSArray", dy: "LNSArray",
                         w: "LNSArray", m: "LNSArray | None", epilogue):
        """Backward-weight ⊞-MAC with the ⊞-SGD update fused at flush.

        ``dW = Xᵀ ⊞-MAC dY`` is consumed by the update (``epilogue``: a
        :class:`~repro.core.sgd.UpdateEpilogue`) against the resident
        ``w``/``m`` planes in a single pass — the gradient never
        round-trips through memory.  Returns ``(w_new, m_new)``
        (``m_new is None`` without momentum).  Bit-identical to
        ``matmul_dw`` + ``core.sgd.apply_update_codes``.
        """
        if self.backend == "pallas":
            from ..kernels.lns_matmul import lns_matmul_dw_update_kernel
            bk, bn, bm = self._op_blocks("dw", x.shape[1], dy.shape[1],
                                         x.shape[0])
            out = lns_matmul_dw_update_kernel(
                x, dy, w=w, m=m, epilogue=epilogue, fmt=self.fmt,
                spec=self.spec, block_k=bk, block_n=bn, block_m=bm,
                interpret=self._interp())
        else:
            from .sgd import apply_update_codes
            g = self.matmul_dw(x, dy)
            out = apply_update_codes(w, g, m, epilogue,
                                     _cached_engine(self.spec, self.fmt))
        if _obs.scope_active():
            _obs.observe_codes(out[0], self.fmt, op="epi_dw_update")
        return out

    def fused_update(self, w: "LNSArray", g: "LNSArray",
                     m: "LNSArray | None", epilogue):
        """One-pass elementwise fused ⊞-SGD update: ``(w, m, g) → (w', m')``.

        The epilogue of gradients that are *not* a dW flush: bias ⊞-fold
        gradients, and — under data parallelism — the already-⊞-combined
        replicated gradients of the deterministic reduce
        (``distributed/lns_dp.py`` applies it after the combine, keeping
        the reduction-order contract untouched).  Bit-identical to
        ``core.sgd.apply_update_codes``.
        """
        if self.backend == "pallas":
            from ..kernels.lns_matmul import lns_fused_update_kernel
            out = lns_fused_update_kernel(
                w, g, m=m, epilogue=epilogue, fmt=self.fmt, spec=self.spec,
                interpret=self._interp())
        else:
            from .sgd import apply_update_codes
            out = apply_update_codes(w, g, m, epilogue,
                                     _cached_engine(self.spec, self.fmt))
        if _obs.scope_active():
            _obs.observe_codes(out[0], self.fmt, op="epi_update")
        return out
