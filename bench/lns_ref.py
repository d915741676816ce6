"""Plain log-domain number system (LNS) arithmetic, written from the paper.

This is the benchmark's own statement of the arithmetic that a
configuration runs: arXiv:1910.09876, Sec. 2-4.  A real ``v`` is carried as
``(code, sign)``: ``code = round(log2|v| * 2**qf)`` saturated to
``qi + qf`` magnitude bits, the most negative code reserved for zero, and
``sign = 1`` for a negative value.  ⊞ is ``max + Δ±(|X - Y|)``, with Δ
read from a table of ``d_max / r`` nearest samples.  A ⊞-MAC folds its
products left to right over the contraction axis, from an accumulator that
holds zero: the sequential MAC of a scalar pipeline.

Nothing here imports the program under test.  The references of the
benchmark's cells are built from these functions, and nothing else.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Fmt:
    """Fixed point of the log-magnitude: ``qi`` integer, ``qf`` fraction
    bits (the paper's W_log = 2 + qi + qf)."""

    qi: int
    qf: int

    @property
    def scale(self) -> int:
        return 1 << self.qf

    @property
    def code_max(self) -> int:
        return (1 << (self.qi + self.qf)) - 1

    @property
    def zero(self) -> int:
        return -(1 << (self.qi + self.qf))

    @property
    def min_nonzero(self) -> int:
        return self.zero + 1

    def to_code(self, log2_mag: float) -> int:
        c = int(round(log2_mag * self.scale))
        return max(self.min_nonzero, min(self.code_max, c))


#: The paper's formats (Sec. 5): 16-bit and 12-bit words.
FORMATS = {"lns16": Fmt(4, 10), "lns12": Fmt(4, 6)}


class Delta:
    """Δ± by nearest sample of a table over ``[0, d_max)`` at step ``r``;
    Δ = 0 beyond the table.  Δ-(0) is the flush-to-zero sentinel: opposite
    operands within half a step of each other cancel (paper Sec. 5)."""

    def __init__(self, fmt: Fmt, d_max: float, r: float):
        self.fmt = fmt
        self.r_code = int(round(r * fmt.scale))
        if self.r_code < 1 or abs(r * fmt.scale - self.r_code) > 1e-9:
            raise ValueError(f"r={r} is not on the qf={fmt.qf} grid")
        n = int(round(d_max / r))
        d = np.arange(n, dtype=np.float64) * r
        self.underflow = -(1 << (fmt.qi + fmt.qf + 2))
        self.plus_tab = [int(v) for v in
                         np.round(np.log2(1.0 + np.exp2(-d)) * fmt.scale)]
        minus = [self.underflow]
        if n > 1:
            minus += [int(v) for v in np.round(
                np.log2(-np.expm1(-d[1:] * np.log(2.0))) * fmt.scale)]
        self.minus_tab = minus

    def _lookup(self, d, tab):
        idx = (d + self.r_code // 2) // self.r_code
        if len(tab) > 32:
            out = jnp.take(np.asarray(tab, np.int32),
                           jnp.clip(idx, 0, len(tab) - 1))
        else:   # a short table as selects, which any backend runs fast
            out = jnp.full(d.shape, tab[0], jnp.int32)
            for j in range(1, len(tab)):
                out = jnp.where(idx >= j, jnp.int32(tab[j]), out)
        return jnp.where(idx >= len(tab), 0, out)

    def plus(self, d):
        return self._lookup(d, self.plus_tab)

    def minus(self, d):
        return jnp.where(d == 0, jnp.int32(self.underflow),
                         self._lookup(d, self.minus_tab))


def delta(fmt_name: str, name: str) -> Delta:
    """The Δ a configuration names: ``lut20`` (d_max 10, r 1/2, the paper's
    default) or ``lut640`` (r 1/64, the paper's softmax table)."""
    r = {"lut20": 0.5, "lut640": 1.0 / 64.0}[name]
    return Delta(FORMATS[fmt_name], 10.0, r)


# A number is a pair (code int32, sign int32), sign 1 = negative.

def encode(v, f: Fmt):
    v = jnp.asarray(v, jnp.float32)
    mag = jnp.abs(v)
    raw = jnp.round(jnp.log2(jnp.where(mag > 0, mag, 1.0)) * f.scale)
    code = jnp.clip(raw.astype(jnp.int32), f.min_nonzero, f.code_max)
    zero = (mag == 0) | (raw < f.min_nonzero)
    return (jnp.where(zero, jnp.int32(f.zero), code),
            (v < 0).astype(jnp.int32))


def decode(a, f: Fmt):
    code, sign = a
    mag = jnp.exp2(code.astype(jnp.float32) / f.scale)
    mag = jnp.where(code == f.zero, 0.0, mag)
    return jnp.where(sign == 1, -1.0, 1.0) * mag


def _sat(code, f: Fmt):
    code = jnp.minimum(code, f.code_max)
    return jnp.where(code < f.min_nonzero, jnp.int32(f.zero), code)


def mul(a, b, f: Fmt):
    """⊡: add the codes, xor the signs."""
    zero = (a[0] == f.zero) | (b[0] == f.zero)
    code = jnp.where(zero, jnp.int32(f.zero), _sat(a[0] + b[0], f))
    sign = jnp.where(zero, 0, a[1] ^ b[1])
    return code, sign


def add(a, b, dl: Delta):
    """⊞ (eq. 3): the larger code plus Δ± of the difference; x ⊞ 0 = x."""
    f = dl.fmt
    (ac, as_), (bc, bs) = a, b
    d = jnp.abs(ac - bc)
    same = as_ == bs
    code = _sat(jnp.maximum(ac, bc)
                + jnp.where(same, dl.plus(d), dl.minus(d)), f)
    code = jnp.where(~same & (d == 0), jnp.int32(f.zero), code)
    sign = jnp.where(same, as_, jnp.where(ac > bc, as_, bs))
    code = jnp.where(ac == f.zero, bc, jnp.where(bc == f.zero, ac, code))
    sign = jnp.where(ac == f.zero, bs, jnp.where(bc == f.zero, as_, sign))
    return code, jnp.where(code == f.zero, 0, sign)


def neg(a):
    return a[0], a[1] ^ 1


def sub(a, b, dl: Delta):
    return add(a, neg(b), dl)


def const(log2_mag_code: int, shape=()):
    """A positive constant given by its code."""
    return (jnp.full(shape, log2_mag_code, jnp.int32),
            jnp.zeros(shape, jnp.int32))


def mac(x, w, dl: Delta):
    """(M, K) ⊞-MAC (K, N) → (M, N): ``acc = acc ⊞ x[:, k] ⊡ w[k, :]``
    for k = 0 .. K-1, from an accumulator that holds zero."""
    f = dl.fmt
    m, n = x[0].shape[0], w[0].shape[1]
    acc = (jnp.full((m, n), f.zero, jnp.int32), jnp.zeros((m, n), jnp.int32))

    def step(acc, k):
        xk, wk = k
        prod = mul((xk[0][:, None], xk[1][:, None]),
                   (wk[0][None, :], wk[1][None, :]), f)
        return add(acc, prod, dl), None

    xs = ((x[0].T, x[1].T), (w[0], w[1]))
    out, _ = jax.lax.scan(step, acc, xs)
    return out


def fold(a, axis: int, dl: Delta):
    """Sequential ⊞ over ``axis`` (left to right, from zero)."""
    f = dl.fmt
    code, sign = (jnp.moveaxis(t, axis, 0) for t in a)
    init = (jnp.full(code.shape[1:], f.zero, jnp.int32),
            jnp.zeros(code.shape[1:], jnp.int32))
    out, _ = jax.lax.scan(lambda acc, t: (add(acc, t, dl), None), init,
                          (code, sign))
    return out


def fold_tree(a, axis: int, dl: Delta):
    """⊞ over ``axis`` as a balanced tree: zero-pad to a power of two,
    then add the first half to the second until one slot is left."""
    f = dl.fmt
    code, sign = (jnp.moveaxis(t, axis, 0) for t in a)
    n = 1 << max(0, math.ceil(math.log2(code.shape[0])))
    pad = [(0, n - code.shape[0])] + [(0, 0)] * (code.ndim - 1)
    code = jnp.pad(code, pad, constant_values=f.zero)
    sign = jnp.pad(sign, pad)
    while code.shape[0] > 1:
        h = code.shape[0] // 2
        code, sign = add((code[:h], sign[:h]), (code[h:], sign[h:]), dl)
    return code[0], sign[0]


def transpose(a):
    return a[0].T, a[1].T
