"""paper-mlp (784-100-10): the system under test, its plain reference, and
its counts.

``System`` drives the program's own step, ``LNSMLP.train_step`` on one
chip or ``LNSDataParallelMLP.train_step`` on a ``data`` mesh, on weight
codes this file makes from the seed.  ``reference`` is the paper's
end-to-end log-domain training step written out here from its equations
(10)-(14) with ``bench/lns_ref.py``: every quantity an LNS code, every
matmul a sequential ⊞-MAC, SGD with weight decay in the log domain, and
on a mesh the canonical schedule of the deterministic all-reduce (the
batch cut into ``grad_segments`` contiguous segments, each segment's
partials folded in segment order).  It imports nothing of the program, so
a sound run of the program agrees with it code for code.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import lns_ref as L
from common import leaf_id, sub_seed

LEAVES = ("w1", "b1", "w2", "b2")


def _shapes(c):
    i, h, o = c["n_in"], c["n_hidden"], c["n_out"]
    return {"w1": ((i, h), math.sqrt(2.0 / i)), "b1": ((h,), None),
            "w2": ((h, o), math.sqrt(2.0 / h)), "b2": ((o,), None)}


def make_params(c: dict, seed: int, fmt_name: str):
    """Weight codes on the device, from the seed, in one jitted call: He
    normal in the log domain (eq. 12), biases zero."""
    f = L.FORMATS[fmt_name]
    key = jax.random.key(sub_seed(seed, "weights"))

    @jax.jit
    def make(key):
        out = {}
        for k, (shape, std) in _shapes(c).items():
            if std is None:
                out[k] = (jnp.full(shape, f.zero, jnp.int32),
                          jnp.zeros(shape, jnp.int32))
                continue
            kk = jax.random.fold_in(key, leaf_id(k))
            n = jax.random.normal(jax.random.fold_in(kk, 0), shape)
            y = jnp.log2(jnp.maximum(jnp.abs(n), 1e-30)) + math.log2(std)
            code = jnp.clip(jnp.round(y * f.scale).astype(jnp.int32),
                            f.min_nonzero, f.code_max)
            sign = jax.random.bernoulli(jax.random.fold_in(kk, 1), 0.5,
                                        shape).astype(jnp.int32)
            out[k] = (code, sign)
        return out
    return make(key)


def _host(params) -> dict:
    return {k: (np.asarray(c), np.asarray(s)) for k, (c, s) in params.items()}


def observe(c, seed, params, fmt_name, which) -> dict:
    """What the check compares, from codes on the host: per-leaf norms of
    the change from the initial weights (``grad``: the gradient the update
    applied, ``(w0 - w1 / (1 - lr*wd)) / lr``), and the codes."""
    f = L.FORMATS[fmt_name]
    p0 = _host(make_params(c, seed, fmt_name))
    p = _host(params)

    def val(a):
        code, sign = a
        mag = np.where(code == f.zero, 0.0,
                       np.exp2(code.astype(np.float64) / f.scale))
        return np.where(sign == 1, -mag, mag)

    lr, wd = c["lr"], c["weight_decay"]
    out = {"codes": {k: p[k] for k in LEAVES}}
    if which == "grad":
        out["norms"] = {k: float(np.linalg.norm(
            (val(p0[k]) - val(p[k]) / (1 - lr * wd)) / lr)) for k in LEAVES}
    else:
        out["norms"] = {k: float(np.linalg.norm(val(p[k]) - val(p0[k])))
                        for k in LEAVES}
    return out


# ----------------------------------------------------------- program --
class System:
    """The program's paper-MLP train step.

    ``variant``: ``control`` runs the program's own 12-bit LNS path,
    ``half_batch`` feeds half the rows, ``unchanged`` keeps the state the
    step was given."""

    def __init__(self, c: dict, cell: dict, seed: int, variant=None,
                 devices=None):
        from repro.core.lns import LNSArray
        from repro.paper.mlp import MLPConfig, make_mlp

        plan = cell["plan"]
        self.fmt_name = "lns12" if variant == "control" else c["format"]
        if variant == "control":
            plan += ",fmt=lns12"
        chips = cell["chips"]
        self.model = make_mlp("lns", MLPConfig(
            n_in=c["n_in"], n_hidden=c["n_hidden"], n_out=c["n_out"],
            lr=c["lr"], weight_decay=c["weight_decay"],
            momentum=c["momentum"], spec=plan, data_parallel=chips))
        inner = getattr(self.model, "inner", self.model)
        self._lanes = inner.lanes()
        self.c, self.seed, self.variant = c, seed, variant
        p = make_params(c, seed, self.fmt_name)
        self.params = {k: LNSArray(code, sign.astype(jnp.int8))
                       for k, (code, sign) in p.items()}

    def lanes(self) -> dict:
        return self._lanes

    def step(self, batch):
        x, y = batch["x"], batch["y"]
        if self.variant == "half_batch":
            x, y = x[: len(x) // 2], y[: len(y) // 2]
        new, loss = self.model.train_step(self.params, x, y)
        if self.variant != "unchanged":
            self.params = new
        return loss

    def block(self):
        jax.block_until_ready(self.params)

    def observe(self, which: str) -> dict:
        p = {k: (a.code, a.sign.astype(jnp.int32))
             for k, a in self.params.items()}
        return observe(self.c, self.seed, p, self.fmt_name, which)

    def free(self):
        self.params = self.model = None


# --------------------------------------------------------- reference --
def _bcast(a, shape):
    return jnp.broadcast_to(a[0], shape), jnp.broadcast_to(a[1], shape)


def _llrelu(a, beta: int, f):
    code, sign = a
    shifted = code + beta
    shifted = jnp.where(shifted < f.min_nonzero, f.zero, shifted)
    code = jnp.where(code == f.zero, f.zero,
                     jnp.where(sign == 1, shifted, code))
    return code, sign


def _log_softmax(a, dl: L.Delta):
    """Eq. 14: recentre at the signed max, scale by log2 e, read the value
    as the log-magnitude of e^a, ⊞-sum (balanced tree), subtract."""
    f = dl.fmt
    code, sign = a
    key = jnp.where(sign == 0, code + (1 << 30), -code - (1 << 30))
    idx = jnp.argmax(key, axis=-1)[..., None]
    m = (jnp.take_along_axis(code, idx, -1), jnp.take_along_axis(sign, idx,
                                                                  -1))
    a = L.sub(a, _bcast(m, code.shape), dl)
    t = L.mul(a, L.const(f.to_code(math.log2(math.log2(math.e)))), f)
    v = jnp.round(jnp.exp2(t[0].astype(jnp.float32) / f.scale + f.qf))
    v = jnp.minimum(v.astype(jnp.int32), f.code_max)
    v = jnp.where(t[0] == f.zero, 0, v)
    e = jnp.maximum(jnp.where(t[1] == 1, -v, v), f.min_nonzero)
    z = L.fold_tree((e, jnp.zeros_like(e)), e.ndim - 1, dl)
    logp = jnp.clip(e - z[0][..., None], f.min_nonzero, 0)
    return logp, jnp.zeros_like(logp)


def _update(w, g, lr_code, wd_code, dl):
    f = dl.fmt
    w = L.sub(w, L.mul(L.const(lr_code), g, f), dl)
    return L.sub(w, L.mul(L.const(wd_code), w, f), dl)


def ref_step(c, p, xb, yb, fmt_name, segments=None, exchange=True):
    """One training step of the paper's arithmetic; ``segments`` cuts the
    batch for the deterministic all-reduce, and ``exchange=False`` keeps
    only segment 0's gradient, as a device that never saw the others'."""
    f = L.FORMATS[fmt_name]
    dl, dsm = L.delta(fmt_name, c["delta"]), L.delta(fmt_name,
                                                     c["softmax_delta"])
    beta = f.to_code(math.log2(c["leaky_relu_alpha"]))
    lr_code = f.to_code(math.log2(c["lr"]))
    wd_code = f.to_code(math.log2(c["lr"] * c["weight_decay"]))
    x = L.encode(xb, f)
    z1 = L.add(L.mac(x, p["w1"], dl), _bcast(p["b1"], (len(xb),
                                                       c["n_hidden"])), dl)
    a1 = _llrelu(z1, beta, f)
    z2 = L.add(L.mac(a1, p["w2"], dl), _bcast(p["b2"], (len(xb),
                                                       c["n_out"])), dl)
    logp = _log_softmax(z2, dsm)
    onehot = yb[:, None] == jnp.arange(c["n_out"])
    d2 = L.sub(logp, (jnp.where(onehot, 0, f.zero), jnp.zeros_like(
        logp[0])), dsm)
    picked = jnp.take_along_axis(logp[0], yb[:, None], -1)[:, 0]
    d1 = L.mul(L.mac(d2, L.transpose(p["w2"]), dl),
               (jnp.where(z1[1] == 1, beta, 0), jnp.zeros_like(z1[1])), f)
    if segments is None:
        loss = -jnp.mean(picked.astype(jnp.float32) / f.scale) * math.log(2)
        g = {"w1": L.mac(L.transpose(x), d1, dl),
             "b1": L.fold_tree(d1, 0, dl),
             "w2": L.mac(L.transpose(a1), d2, dl),
             "b2": L.fold_tree(d2, 0, dl)}
    else:
        seg = len(xb) // segments
        per = (picked.astype(jnp.float32) / f.scale).reshape(segments, seg)
        loss = -jnp.mean(jnp.mean(per, 1)) * math.log(2)

        def cut(a, s):
            return a[0][s * seg:(s + 1) * seg], a[1][s * seg:(s + 1) * seg]

        parts = {k: [] for k in LEAVES}
        for s in range(segments if exchange else 1):
            xs, a1s, d1s, d2s = (cut(t, s) for t in (x, a1, d1, d2))
            parts["w1"].append(L.mac(L.transpose(xs), d1s, dl))
            parts["b1"].append(L.fold(d1s, 0, dl))
            parts["w2"].append(L.mac(L.transpose(a1s), d2s, dl))
            parts["b2"].append(L.fold(d2s, 0, dl))
        g = {k: L.fold((jnp.stack([q[0] for q in v]),
                        jnp.stack([q[1] for q in v])), 0, dl)
             for k, v in parts.items()}
    new = {k: _update(p[k], g[k], lr_code, wd_code, dl) for k in LEAVES}
    return new, loss


def _segments(cell):
    return cell.get("grad_segments")


def reference(c: dict, cell: dict, seed: int, batches: list,
              fmt_name: str = None) -> dict:
    """The first ``len(batches)`` steps of the paper's arithmetic: each
    step's loss, and the observations after step 1 and the last step."""
    fmt_name = fmt_name or c["format"]
    step = jax.jit(functools.partial(ref_step, c, fmt_name=fmt_name,
                                     segments=_segments(cell)))
    p = make_params(c, seed, fmt_name)
    out = {"losses": []}
    for i, b in enumerate(batches):
        p, loss = step(p, jnp.asarray(b["x"]), jnp.asarray(b["y"]))
        out["losses"].append(float(loss))
        if i == 0:
            out["grad"] = observe(c, seed, p, fmt_name, "grad")
    out["change"] = observe(c, seed, p, fmt_name, "change")
    return out


class RefSystem:
    """The reference put in the program's place, with a fault planted in
    it: ``no_exchange`` (each device keeps its own segment's gradient)."""

    def __init__(self, c, cell, seed, variant):
        self.c, self.seed, self.fmt_name = c, seed, c["format"]
        self.params = make_params(c, seed, c["format"])
        self._step = jax.jit(functools.partial(
            ref_step, c, fmt_name=c["format"], segments=_segments(cell),
            exchange=variant != "no_exchange"))

    def lanes(self):
        return {}

    def step(self, batch):
        self.params, loss = self._step(self.params, jnp.asarray(batch["x"]),
                                       jnp.asarray(batch["y"]))
        return loss

    def block(self):
        jax.block_until_ready(self.params)

    def observe(self, which):
        return observe(self.c, self.seed, self.params, self.fmt_name, which)

    def free(self):
        self.params = None


# ------------------------------------------------------------- counts --
def counts(c: dict, traffic, chips: int = 1) -> dict:
    """Per step: samples, the ⊞-MAC launches (M, K, N) that one chip runs,
    and the model operations of the whole step (a ⊞-MAC counts 2; the
    forward and the three backward products)."""
    i, h, o = c["n_in"], c["n_hidden"], c["n_out"]
    b = traffic.items_per_step
    m = b // chips
    calls = [(m, i, h), (m, h, o),      # forward
             (m, o, h),                 # dX of the output layer
             (i, m, h), (h, m, o)]      # dW of both layers, over the rows
    per_sample = sum(x * y * z for x, y, z in calls) // m
    return {"items_per_step": b, "mac_calls": calls,
            "macs": per_sample * b, "model_ops": 2 * per_sample * b}
