"""The paper's MLP (784–100–K) in three arithmetic backends (Sec. 4/5).

* ``float`` — fp32 linear-domain reference.
* ``fxp``   — linear-domain fixed point (12/16-bit), hand backprop.
* ``lns``   — end-to-end log-domain fixed point (12/16-bit, LUT or
              bit-shift Δ), hand backprop: every forward/backward/update
              quantity is an LNS code; no float enters the training path
              (the CE loss value is a monitoring readout only).

Backprop follows eq. (10)-(14): δ2 = P ⊟ Y, gW2 = a1ᵀ ⊡⊞ δ2, δ1 =
(δ2 ⊡⊞ W2ᵀ) ⊡ llReLU'(z1), gW1 = xᵀ ⊡⊞ δ1, SGD per core/sgd.py.

All LNS matmuls (forward *and* the three backward products) route through
per-layer :class:`~repro.core.spec.LNSRuntime`\\ s resolved from
``MLPConfig.spec`` — a :class:`~repro.core.plan.NumericsPlan` mapping the
MLP's layer paths (``"hidden"``: w1/b1, ``"out"``: w2/b2) to specs.  A
bare spec string is a plan with no overrides (every layer shares one
runtime — bit-identical to the pre-plan single-runtime path); a plan like
``"lns16-train-pallas;hidden=fmt:lns12"`` trains the hidden layer in
lns12 while the softmax-critical output layer stays lns16, with exact
integer barrel-shift conversions (:func:`~repro.core.lns.convert_format`)
at the layer boundaries.  ``backend="emulate"`` runs the pure-jnp
sequential MAC, ``"pallas"`` the blocked TPU kernels (interpret mode off the
TPU); the two backends are bit-exact down to the last weight code — also
under mixed-format plans.  The legacy loose knobs (``matmul_backend=`` /
``reduce_mode=`` / ``grad_segments=``) still construct, with a
``DeprecationWarning`` pointing at the spec field they fold into.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import warnings
import weakref
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as _obs
from ..obs.trace import phase_scope
from ..resil import inject as _inj

from ..core import (DELTA_BITSHIFT, DELTA_DEFAULT, DELTA_EXACT,
                    DELTA_SOFTMAX, FXP12, FXP16, LNS12, LNS16, DeltaEngine,
                    DeltaSpec, LNSArray, LNSMatmulBackend, LogSGDConfig,
                    NumericsPlan, NumericsSpec, UpdateEpilogue,
                    apply_update, beta_code, boxabs_max, boxdot, boxsum,
                    ce_grad_init, ce_loss_readout, convert_format, decode,
                    encode, he_sigma, llrelu, llrelu_grad,
                    llrelu_grad_from_sign, log_normal_init,
                    log_softmax_lns, scalar, zeros)
from ..core.linear_fixed import (fxp_affine, fxp_decode, fxp_encode,
                                 fxp_leaky_relu, fxp_leaky_relu_grad,
                                 fxp_matmul, fxp_mul, fxp_sat)
from ..core.spec import LNSRuntime

HIDDEN = 100
ALPHA = 0.01  # leaky-ReLU slope [20]

#: The paper MLP's layer paths: what NumericsPlan glob patterns match.
LAYER_PATHS = ("hidden", "out")
#: Parameter → owning layer path (the unit of per-layer arithmetic).
PARAM_LAYER = {"w1": "hidden", "b1": "hidden", "w2": "out", "b2": "out"}

_APPROX_DELTA = {"lut": DELTA_DEFAULT, "bitshift": DELTA_BITSHIFT,
                 "exact": DELTA_EXACT}


@dataclasses.dataclass(frozen=True)
class MLPConfig:
    n_in: int = 784
    n_hidden: int = HIDDEN
    n_out: int = 10
    lr: float = 0.01
    weight_decay: float = 0.0
    momentum: float = 0.0           # lns only: ⊞-momentum (LogSGDConfig)
    bits: int = 16                 # 12 or 16
    approx: str = "lut"            # 'lut' | 'bitshift' | 'exact' (lns only)
    stochastic_round: bool = False  # fxp only: SR on the weight update
                                    # (Gupta et al. 2015; beyond-paper)
    spec: Any = None                # NumericsPlan | NumericsSpec | plan or
                                    # spec string | None; None → derived
                                    # from bits/approx (end-to-end train
                                    # spec, emulate).  Normalized to a
                                    # NumericsPlan in __post_init__.
    matmul_block: int = 128         # kernel tile edge (compiled
                                    # launches fit it to the chip's
                                    # (8, 128) tiling; CPU tests pass
                                    # small tiles explicitly)
    fused: bool = True              # lns only: flush-time kernel epilogues
                                    # (bias/llrelu/requantize in the fwd
                                    # kernel, ⊞-SGD in the dW flush) —
                                    # bit-identical to the unfused
                                    # composition; False = separate-pass
                                    # reference path (benchmarks)
    data_parallel: int = 1          # lns only: devices on the 'data' axis
    faults: Any = None              # lns only: FaultPlan | plan string |
                                    # None (resil/inject).  None → no
                                    # injection, graphs bit-identical to a
                                    # fault-free build.  Normalized to a
                                    # FaultPlan in __post_init__.
    # -- legacy loose knobs, deprecated: fold into ``spec`` ----------------
    matmul_backend: dataclasses.InitVar[Any] = None   # → spec.backend
    reduce_mode: dataclasses.InitVar[Any] = None      # → spec.reduce.mode
    grad_segments: dataclasses.InitVar[Any] = None    # → spec.reduce
                                                      #   .grad_segments

    def __post_init__(self, matmul_backend, reduce_mode, grad_segments):
        spec = self.spec
        if spec is not None:
            spec = NumericsPlan.parse(spec)
        else:
            # The paper's end-to-end log-domain training arithmetic at
            # this config's format / Δ approximation.
            spec = NumericsPlan(NumericsSpec(
                fmt=self.lns_fmt, delta_spec=_APPROX_DELTA[self.approx],
                quantize="params+acts+grads", compute_dtype="float32"))
        # A legacy value equal to what the spec already resolves to is a
        # no-op and stays silent — this also keeps dataclasses.replace()
        # warning-free (replace() re-passes the property-read values of
        # the InitVar names, which by construction equal the spec's).
        current = {"backend": spec.backend, "reduce.mode": spec.reduce.mode,
                   "reduce.grad_segments": spec.reduce.grad_segments}
        legacy = {k: v for k, v in (("backend", matmul_backend),
                                    ("reduce.mode", reduce_mode),
                                    ("reduce.grad_segments", grad_segments))
                  if v is not None and v != current[k]}
        if legacy:
            spec = spec.with_(**legacy)
            warnings.warn(
                f"MLPConfig(matmul_backend=/reduce_mode=/grad_segments=) "
                f"are deprecated; pass the unified descriptor instead: "
                f"MLPConfig(spec={str(spec)!r})",
                DeprecationWarning, stacklevel=3)
        object.__setattr__(self, "spec", spec)
        object.__setattr__(self, "faults", _inj.FaultPlan.parse(self.faults))

    @property
    def lns_fmt(self):
        if isinstance(self.spec, (NumericsSpec, NumericsPlan)) \
                and self.spec.fmt is not None:
            return self.spec.fmt
        return LNS16 if self.bits == 16 else LNS12

    @property
    def fxp_fmt(self):
        return FXP16 if self.bits == 16 else FXP12

    @property
    def delta_spec(self) -> DeltaSpec:
        if (isinstance(self.spec, (NumericsSpec, NumericsPlan))
                and self.spec.delta_spec is not None):
            return self.spec.delta_spec
        return _APPROX_DELTA[self.approx]

    @property
    def softmax_spec(self) -> DeltaSpec:
        # Paper: softmax is approximation-sensitive → r = 1/64 table,
        # also when the rest of the net uses bit-shifts.
        return DELTA_EXACT if self.delta_spec.kind == "exact" \
            else DELTA_SOFTMAX

    def plan(self) -> NumericsPlan:
        """The completed per-layer :class:`NumericsPlan`.

        The paper MLP always runs the end-to-end ⊞-MAC path, so a plan
        whose default spec has no explicit fmt/Δ (e.g. ``"fp32"`` passed
        through) is completed from ``bits`` / ``approx`` before
        resolution; per-layer rules apply on top of the completed default.
        """
        plan = self.spec
        if plan.fmt is None or plan.delta_spec is None:
            plan = plan.with_(fmt=self.lns_fmt, delta_spec=self.delta_spec)
        return plan

    def layer_runtime(self, path: str) -> LNSRuntime:
        """The resolved runtime of layer ``path`` at this tile size."""
        return self.plan().runtime_for(path, block_m=self.matmul_block,
                                       block_n=self.matmul_block,
                                       block_k=self.matmul_block)

    def runtime(self) -> LNSRuntime:
        """The *default* resolved runtime (shared by every layer no plan
        rule overrides); per-layer consumers use :meth:`layer_runtime`."""
        return self.plan().runtime(block_m=self.matmul_block,
                                   block_n=self.matmul_block,
                                   block_k=self.matmul_block)


# Legacy read access (cfg.matmul_backend etc.): views over the spec.  The
# names double as deprecated constructor keywords (InitVars) above, so the
# properties are attached post-class.
MLPConfig.matmul_backend = property(lambda self: self.spec.backend)
MLPConfig.reduce_mode = property(lambda self: self.spec.reduce.mode)
MLPConfig.grad_segments = property(
    lambda self: self.spec.reduce.grad_segments)


# ---------------------------------------------------------------- float --
class FloatMLP:
    def __init__(self, cfg: MLPConfig):
        self.cfg = cfg

    def init(self, key):
        k1, k2 = jax.random.split(key)
        c = self.cfg
        return dict(
            w1=he_sigma(c.n_in) * jax.random.normal(k1, (c.n_in, c.n_hidden)),
            b1=jnp.zeros((c.n_hidden,)),
            w2=he_sigma(c.n_hidden)
            * jax.random.normal(k2, (c.n_hidden, c.n_out)),
            b2=jnp.zeros((c.n_out,)),
        )

    @functools.partial(jax.jit, static_argnums=0)
    def train_step(self, params, xb, yb):
        c = self.cfg

        def loss_fn(p):
            z1 = xb @ p["w1"] + p["b1"]
            a1 = jnp.where(z1 > 0, z1, ALPHA * z1)
            z2 = a1 @ p["w2"] + p["b2"]
            lp = jax.nn.log_softmax(z2)
            # Sum-reduction over the minibatch (see module docstring):
            # gradients are per-sample outer products accumulated by the
            # MAC array — no 1/B rescale, which would underflow the
            # linear fixed-point resolution at lr=0.01.
            nll = -jnp.take_along_axis(lp, yb[:, None], axis=1).sum()
            return nll

        loss, g = jax.value_and_grad(loss_fn)(params)
        params = jax.tree.map(
            lambda w, gw: w - c.lr * (gw + c.weight_decay * w), params, g)
        return params, loss

    @functools.partial(jax.jit, static_argnums=0)
    def predict(self, params, xb):
        z1 = xb @ params["w1"] + params["b1"]
        a1 = jnp.where(z1 > 0, z1, ALPHA * z1)
        return jnp.argmax(a1 @ params["w2"] + params["b2"], axis=-1)


# ------------------------------------------------------------------ fxp --
class FxpMLP:
    """Linear-domain fixed point; the paper's Table-1 baseline.

    The softmax/CE-gradient is evaluated at float precision on decoded
    logits and re-encoded (a fine exp-LUT in hardware); the paper found the
    softmax to be the precision-critical block, which this mirrors.
    """

    def __init__(self, cfg: MLPConfig):
        self.cfg = cfg
        self.fmt = cfg.fxp_fmt

    def init(self, key):
        k1, k2 = jax.random.split(key)
        c, f = self.cfg, self.fmt
        return dict(
            w1=fxp_encode(he_sigma(c.n_in)
                          * jax.random.normal(k1, (c.n_in, c.n_hidden)), f),
            b1=jnp.zeros((c.n_hidden,), jnp.int32),
            w2=fxp_encode(he_sigma(c.n_hidden)
                          * jax.random.normal(k2, (c.n_hidden, c.n_out)), f),
            b2=jnp.zeros((c.n_out,), jnp.int32),
        )

    @functools.partial(jax.jit, static_argnums=0)
    def train_step(self, params, xb, yb, key=None):
        c, f = self.cfg, self.fmt
        alpha = fxp_encode(jnp.float32(ALPHA), f)
        x = fxp_encode(xb, f)
        z1 = fxp_affine(x, params["w1"], params["b1"], f)
        a1 = fxp_leaky_relu(z1, alpha, f)
        z2 = fxp_affine(a1, params["w2"], params["b2"], f)
        # float softmax on decoded logits (see class docstring);
        # sum-reduction over the minibatch (no 1/B — see mlp.py docstring)
        p = jax.nn.softmax(fxp_decode(z2, f), axis=-1)
        onehot = jax.nn.one_hot(yb, c.n_out)
        d2 = fxp_encode(p - onehot, f)
        gw2 = fxp_matmul(a1.T, d2, f)
        gb2 = fxp_sat(jnp.sum(d2, axis=0), f)
        bp = fxp_matmul(d2, params["w2"].T, f)
        d1 = fxp_mul(bp, fxp_leaky_relu_grad(z1, alpha, f), f)
        gw1 = fxp_matmul(x.T, d1, f)
        gb1 = fxp_sat(jnp.sum(d1, axis=0), f)
        lr = fxp_encode(jnp.float32(c.lr), f)
        if c.stochastic_round and key is not None:
            keys = iter(jax.random.split(key, 4))

            def upd(w, g):
                # raw product carries 2·bf fraction bits; round the low bf
                # bits stochastically so sub-resolution updates survive in
                # expectation (Gupta et al. 2015).
                raw = lr * g
                low = raw & (f.scale - 1)
                base = raw >> f.bf
                r = jax.random.randint(next(keys), w.shape, 0, f.scale)
                step = base + (low > r).astype(jnp.int32)
                return fxp_sat(w - step, f)
        else:
            def upd(w, g):
                return fxp_sat(w - fxp_mul(lr, g, f), f)

        new = dict(w1=upd(params["w1"], gw1), b1=upd(params["b1"], gb1),
                   w2=upd(params["w2"], gw2), b2=upd(params["b2"], gb2))
        lp = jax.nn.log_softmax(fxp_decode(z2, f))
        nll = -jnp.take_along_axis(lp, yb[:, None], axis=1).mean()
        return new, nll

    @functools.partial(jax.jit, static_argnums=0)
    def predict(self, params, xb):
        f = self.fmt
        alpha = fxp_encode(jnp.float32(ALPHA), f)
        x = fxp_encode(xb, f)
        z1 = fxp_affine(x, params["w1"], params["b1"], f)
        a1 = fxp_leaky_relu(z1, alpha, f)
        z2 = fxp_affine(a1, params["w2"], params["b2"], f)
        return jnp.argmax(z2, axis=-1)

    @functools.partial(jax.jit, static_argnums=(0, 2))
    def apply_decay(self, params, every: int):
        """Periodic weight decay: the per-step constant lr·λ underflows
        narrow fixed point (code 0 at bf=7), so decay is applied every
        ``every`` steps with the representable constant every·lr·λ — the
        12-bit runs *require* this ("larger regularization constant",
        paper Sec. 5)."""
        f, c = self.fmt, self.cfg
        wd = fxp_encode(jnp.float32(every * c.lr * c.weight_decay), f)
        return {k: fxp_sat(w - fxp_mul(wd, w, f), f)
                for k, w in params.items()}


# ------------------------------------------------------------------ lns --
def segmented_boxsum(d: LNSArray, num_segments: int, eng) -> LNSArray:
    """Per-segment sequential ⊞-fold over the batch axis: (B, K) → (S, K).

    The bias-gradient side of the DP deterministic-reduce contract
    (``distributed/lns_reduce.py``): slot ``s`` is the sequential fold of
    segment ``s``'s rows only.
    """
    b = d.shape[0]
    seg = b // num_segments
    tail = d.shape[1:]
    parts = LNSArray(d.code.reshape((num_segments, seg) + tail),
                     d.sign.reshape((num_segments, seg) + tail))
    return boxsum(parts, 1, eng, order="sequential")


class LNSMLP:
    """End-to-end log-domain training (the paper's contribution).

    Arithmetic is a *per-layer* property: the config's
    :class:`~repro.core.plan.NumericsPlan` resolves one runtime per layer
    path (``"hidden"``, ``"out"``).  Layers sharing a resolved spec share
    one cached runtime — a bare spec (no plan rules) reproduces the
    single-runtime semantics bit-for-bit.  Activations and
    backpropagated errors crossing a format boundary go through
    :func:`~repro.core.lns.convert_format` (exact integer shifts).
    """

    def __init__(self, cfg: MLPConfig):
        self.cfg = cfg
        self.plan = cfg.plan().validate_paths(LAYER_PATHS)
        self.runtimes = {p: cfg.layer_runtime(p) for p in LAYER_PATHS}
        self.fmts = {p: self.runtimes[p].spec.fmt for p in LAYER_PATHS}
        self.engs = {p: self.runtimes[p].delta_engine for p in LAYER_PATHS}
        # Fault surface (resil/inject): Δ-LUT corruption is a build-time
        # fault, applied to *copies* — the runtime-cached engines are
        # shared across models and must never be mutated.  The corrupted
        # engines feed every shared-jnp ⊞ site (bias-gradient boxsum,
        # boxdot, the unfused update, the DP combine), identically on the
        # emulate and pallas lanes; the matmul kernels' baked tables are
        # out of scope for this fault.  No plan ⇒ the engines pass
        # through untouched (identical objects, identical graphs).
        self.fault_plan = cfg.faults
        if self.fault_plan is not None:
            self.fault_plan.validate_paths(LAYER_PATHS + ("serve",))
            self.engs = {p: _inj.corrupt_engine(self.engs[p],
                                                self.fault_plan, p)
                         for p in LAYER_PATHS}
        # Softmax sits in the output layer: its (approximation-sensitive,
        # r = 1/64) Δ table lives in the *output* format.
        out_delta = self.runtimes["out"].spec.delta_spec
        sm_spec = DELTA_EXACT if out_delta.kind == "exact" else DELTA_SOFTMAX
        self.eng_sm = DeltaEngine(sm_spec, self.fmts["out"])
        self.beta = beta_code(ALPHA, self.fmts["hidden"])
        self.sgd = LogSGDConfig(lr=cfg.lr, weight_decay=cfg.weight_decay,
                                momentum=cfg.momentum)
        # The ⊞-SGD update as static scalar codes, one per layer format —
        # what the fused kernels apply at accumulator flush (and what the
        # fused-update kernel applies after the DP ⊞-combine).  Same
        # scalar() quantization as apply_update → bit-identical updates.
        # lr <= 0 has no scalar code (predict-only / frozen-weight
        # configs): the fused paths fall back to the unfused update.
        self.update_eps = (
            {p: UpdateEpilogue.from_sgd(self.sgd, self.fmts[p])
             for p in LAYER_PATHS} if cfg.lr > 0 else None)
        # Per-parameter views (the unit the DP reduce plans key on).
        self.param_runtimes = {k: self.runtimes[l]
                               for k, l in PARAM_LAYER.items()}
        self.param_engines = {k: self.engs[l]
                              for k, l in PARAM_LAYER.items()}
        self.param_fmts = {k: self.fmts[l] for k, l in PARAM_LAYER.items()}
        # Telemetry eligibility per layer (the plan's `metrics` axis); the
        # master switch is which entry point runs (train_step vs
        # train_step_metrics) — see repro.obs.metrics.
        self.metrics_levels = {p: self.runtimes[p].spec.metrics
                               for p in LAYER_PATHS}
        # train_step's hand-back state: weak references to the leaves its
        # last call returned (it keeps none alive), and how many of its
        # calls donated them.
        self._returned = ()
        self.calls = 0
        self.donated_calls = 0

    def lanes(self) -> dict:
        """Layer path → resolved execution lane, for metrics rows."""
        return {p: self.runtimes[p].lane for p in LAYER_PATHS}

    # -- telemetry gates (no-ops unless a collector is active) -------------
    def _collect(self, layer: str, level: str = "counters") -> bool:
        """Should this layer tap at ``level`` right now?"""
        if not _obs.enabled():
            return False
        mode = self.metrics_levels[layer]
        if mode == "off":
            return False
        return mode == "full" if level == "full" else True

    def _scope(self, layer: str, op: str):
        """Ambient tap scope for ``layer`` — a null context unless a
        collector is live and the layer's spec opted in, so the plain
        train_step never even pushes scope state."""
        if self._collect(layer):
            return _obs.scope(layer, op)
        return contextlib.nullcontext()

    def init(self, key):
        k1, k2 = jax.random.split(key)
        c = self.cfg
        fh, fo = self.fmts["hidden"], self.fmts["out"]
        return dict(
            w1=log_normal_init(k1, (c.n_in, c.n_hidden), he_sigma(c.n_in),
                               fh),
            b1=zeros((c.n_hidden,), fh),
            w2=log_normal_init(k2, (c.n_hidden, c.n_out),
                               he_sigma(c.n_hidden), fo),
            b2=zeros((c.n_out,), fo),
        )

    def init_momentum(self, params):
        """Zero ⊞-momentum state, one slot per parameter in its layer's
        format (``None`` when momentum is off)."""
        if self.sgd.momentum == 0.0:
            return None
        return {k: zeros(params[k].shape, self.param_fmts[k])
                for k in params}

    def _forward(self, params, x: LNSArray):
        """Forward pass; returns (z1_sign, a1 [out fmt], z2).

        ``a1`` is returned already converted to the output layer's format
        — the form both its consumers (the z2 matmul and the dW2 backward
        product) need.  ``z1_sign`` is the post-bias pre-activation sign
        plane, the only piece of z1 backward needs (``llrelu_grad``
        depends on sign(z1) alone).  With ``cfg.fused`` the bias ⊞ /
        llrelu / format conversion run in the forward kernels'
        accumulator flush — one pass per matmul instead of one matmul +
        three elementwise passes — bit-identical to the unfused chain.
        """
        mm_h = self.runtimes["hidden"].matmul
        mm_o = self.runtimes["out"].matmul
        fh, fo = self.fmts["hidden"], self.fmts["out"]
        if self.cfg.fused:
            with self._scope("hidden", "fwd"):  # epi_fwd flush tap
                a1, z1_sign = mm_h.matmul_fused(
                    x, params["w1"], bias=params["b1"],
                    llrelu_beta=self.beta, out_fmt=fo, emit_z_sign=True)
            with self._scope("out", "fwd"):
                z2 = mm_o.matmul_fused(a1, params["w2"], bias=params["b2"])
        else:
            with self._scope("hidden", "fwd"):  # convert_* taps
                z1 = mm_h.affine(x, params["w1"], params["b1"])
                a1 = llrelu(z1, self.beta, fh)
                a1 = convert_format(a1, fh, fo)
            with self._scope("out", "fwd"):
                z2 = mm_o.affine(a1, params["w2"], params["b2"])
            z1_sign = z1.sign
        # Fault sites (no-ops unless a FaultPlan is ambient — identical
        # objects, identical graphs): activation-plane bit flips and
        # stuck-at-saturation lanes land *after* the layer's compute and
        # *before* the obs taps, so the detectors see what the next layer
        # sees.
        a1 = _inj.inject_codes(a1, fo, layer="hidden", site="act")
        z2 = _inj.inject_codes(z2, fo, layer="out", site="act")
        if self._collect("hidden"):
            _obs.observe_codes(a1, fo, layer="hidden", op="act")
        if self._collect("out"):
            _obs.observe_codes(z2, fo, layer="out", op="logits")
        return z1_sign, a1, z2

    def _bwd_core(self, params, xb, yb):
        """Forward + error backprop; returns ``(x, a1, d1, d2, loss)``.

        The shared trunk of every train-step flavor: the gradient *sources*
        (per-layer error planes d1/d2 and the activations they pair with),
        before any dW product — so the fused step can route them into
        dW-update flushes while the unfused/segmented steps materialize
        gradients.
        """
        fh, fo = self.fmts["hidden"], self.fmts["out"]
        mm_o = self.runtimes["out"].matmul
        with self._scope("hidden", "encode"):   # q_* quantization taps
            x = encode(xb, fh)                  # dataset conversion (Sec. 4)
        with phase_scope("fwd"):
            z1_sign, a1, z2 = self._forward(params, x)
            p = log_softmax_lns(z2, self.eng_sm)
        # Δ-LUT occupancy (metrics=full): shadow replay of each forward
        # matmul's exact sequential MAC order — telemetry only, the chain
        # above is what flows on.
        if self._collect("hidden", "full"):
            from ..core.arithmetic import matmul_dhist
            _obs.tap("dhist",
                     matmul_dhist(x, params["w1"], self.engs["hidden"]),
                     layer="hidden", op="fwd")
        if self._collect("out", "full"):
            from ..core.arithmetic import matmul_dhist
            _obs.tap("dhist",
                     matmul_dhist(a1, params["w2"], self.engs["out"]),
                     layer="out", op="fwd")
        d2 = ce_grad_init(p, yb, fo, self.eng_sm)         # (B, K), out fmt
        if self._collect("out"):
            _obs.observe_codes(d2, fo, layer="out", op="dgrad")
        # Sum-reduction over the minibatch, matching the fxp baseline.
        # The transposed MACs run on each layer's backward path (Pallas
        # kernels when that layer's spec says backend=pallas).
        with phase_scope("dx"):
            bp = mm_o.matmul_dx(d2, params["w2"])         # (B, H), out fmt
            with self._scope("hidden", "dx"):   # convert_* taps
                bp = convert_format(bp, fo, fh)
            d1 = boxdot(bp, llrelu_grad_from_sign(z1_sign, self.beta), fh)
        if self._collect("hidden"):
            _obs.observe_codes(d1, fh, layer="hidden", op="dgrad")
        return x, a1, d1, d2, ce_loss_readout(p, yb, fo)

    def _backward(self, params, xb, yb, num_segments=None):
        """Shared backward pass of the single-device and DP train steps.

        ``num_segments=None`` emits fully ⊞-reduced gradients (the
        paper's sequential MAC over the batch); an integer emits
        per-segment partial codes with a leading segment axis — the
        emission side of the deterministic DP all-reduce.  Every gradient
        leaf is in its *own layer's* format (``PARAM_LAYER``).
        """
        eng_h, eng_o = self.engs["hidden"], self.engs["out"]
        mm_h = self.runtimes["hidden"].matmul
        mm_o = self.runtimes["out"].matmul
        x, a1, d1, d2, loss = self._bwd_core(params, xb, yb)
        if num_segments is None:
            grads = dict(w1=mm_h.matmul_dw(x, d1),
                         b1=boxsum(d1, 0, eng_h),
                         w2=mm_o.matmul_dw(a1, d2),
                         b2=boxsum(d2, 0, eng_o))
        else:
            grads = dict(
                w1=mm_h.matmul_dw_partials(x, d1, num_segments),
                b1=segmented_boxsum(d1, num_segments, eng_h),
                w2=mm_o.matmul_dw_partials(a1, d2, num_segments),
                b2=segmented_boxsum(d2, num_segments, eng_o))
        return grads, loss

    def per_segment_grads(self, params, xb, yb, num_segments: int):
        """Per-segment gradient partials (leading segment axis) + loss."""
        return self._backward(params, xb, yb, num_segments)

    def apply_updates(self, params, grads, momentum=None):
        """Pure-LNS SGD, each layer under its own Δ engine/format.

        With ``cfg.fused`` the update runs through each layer backend's
        one-pass fused-update kernel (``LNSMatmulBackend.fused_update``),
        bit-identical to the unfused ``apply_update`` composition — this
        is the post-⊞-combine epilogue of the DP deterministic reduce.
        """
        if self.cfg.fused and self.update_eps is not None:
            # cfg.momentum == 0 with a momentum pytree passed: the
            # unfused path passes the state through untouched — mirror
            # that (the epilogue has no momentum term to feed it to).
            has_mom = self.sgd.momentum != 0.0
            new_p, new_m = {}, ({} if momentum is not None else None)
            for k in params:
                layer = PARAM_LAYER[k]
                m_k = momentum[k] if has_mom and momentum is not None \
                    else None
                with self._scope(layer, f"update.{k}"):  # epi_update tap
                    w_new, m_new = self.runtimes[layer].matmul.fused_update(
                        params[k], grads[k], m_k, self.update_eps[layer])
                new_p[k] = w_new
                if momentum is not None:
                    new_m[k] = m_new if has_mom else momentum[k]
            return new_p, new_m
        new_p, new_m = {}, ({} if momentum is not None else None)
        for layer in LAYER_PATHS:
            keys = [k for k, l in PARAM_LAYER.items() if l == layer]
            sub_m = None if momentum is None \
                else {k: momentum[k] for k in keys}
            p2, m2 = apply_update({k: params[k] for k in keys},
                                  {k: grads[k] for k in keys},
                                  sub_m, self.sgd, self.engs[layer])
            if self._collect(layer):
                for k in keys:
                    _obs.observe_codes(p2[k], self.fmts[layer],
                                       layer=layer, op=f"update.{k}")
            new_p.update(p2)
            if momentum is not None:
                new_m.update(m2)
        return new_p, new_m

    def _step_impl(self, params, xb, yb, momentum=None):
        """The train-step body, shared by :meth:`train_step` (plain) and
        :meth:`train_step_metrics` (collector active) — one trace source,
        so telemetry can never fork the arithmetic."""
        # Weight-code bit flips (fault site; same-object no-op without an
        # ambient FaultPlan): the step trains on the flipped codes, but
        # the *stored* params are untouched — a flip is transient unless
        # the update bakes it in, matching SEU semantics.
        params = _inj.inject_param_codes(params, param_fmts=self.param_fmts,
                                         param_layer=PARAM_LAYER)
        if not self.cfg.fused or self.update_eps is None:
            grads, loss = self._backward(params, xb, yb)
            with phase_scope("update"):
                params, momentum = self.apply_updates(params, grads,
                                                      momentum)
            if momentum is None:
                return params, loss
            return params, momentum, loss
        x, a1, d1, d2, loss = self._bwd_core(params, xb, yb)
        # cfg.momentum == 0 with a momentum pytree passed: pass the
        # state through untouched, exactly like the unfused path.
        has_mom = self.sgd.momentum != 0.0
        new_p = {}
        new_m = {} if momentum is not None else None
        for wk, bk, layer, act, d in (("w1", "b1", "hidden", x, d1),
                                      ("w2", "b2", "out", a1, d2)):
            mm = self.runtimes[layer].matmul
            ep = self.update_eps[layer]
            m_w = momentum[wk] if has_mom and momentum is not None \
                else None
            with phase_scope("dw"), \
                    self._scope(layer, f"update.{wk}"):  # epi_dw_update tap
                w_new, mw_new = mm.matmul_dw_update(act, d, params[wk],
                                                    m_w, ep)
            gb = boxsum(d, 0, self.engs[layer])
            m_b = momentum[bk] if has_mom and momentum is not None \
                else None
            with phase_scope("update"), \
                    self._scope(layer, f"update.{bk}"):  # epi_update tap
                b_new, mb_new = mm.fused_update(params[bk], gb, m_b, ep)
            new_p[wk], new_p[bk] = w_new, b_new
            if momentum is not None:
                new_m[wk] = mw_new if has_mom else momentum[wk]
                new_m[bk] = mb_new if has_mom else momentum[bk]
        if momentum is None:
            return new_p, loss
        return new_p, new_m, loss

    def train_step(self, params, xb, yb, momentum=None):
        """One step; returns (params, loss), or (params, momentum, loss)
        when a momentum pytree is passed (``cfg.momentum > 0``).

        With ``cfg.fused`` (default) the step is one pass per matmul: the
        forward kernels fold bias/llrelu/format conversion into their
        flush, and the weight gradients never materialize — each dW
        kernel's flush applies the ⊞-SGD update (momentum + weight decay)
        against the resident weight/momentum tiles directly.  Bias
        gradients (⊞-folds, not matmuls) go through the standalone
        fused-update kernel.  Bit-identical to the unfused step.

        No collector is active here, so every telemetry gate is
        statically false: the jitted graph has no extra outputs and is
        the same graph as before the obs subsystem existed.

        One program runs, and it donates the params (and momentum) it is
        given.  Passing back the ones this model returned on its last
        call reuses their buffers, and the arrays passed are deleted:
        copy them first to keep them.  Any other params (the ``init``
        ones, a copy, a dict with one leaf swapped) are kept: the step
        copies them on the device and donates the copy.  The call is one
        host span ``repro.train_step`` with the argument ``donated`` 0
        or 1.
        """
        with jax.profiler.TraceAnnotation("repro.train_step") as span:
            donate = self._handed_back(params, momentum)
            span.set_metadata(donated=int(donate))
            if not donate:
                # A real copy, made by the runtime with no program to
                # compile: a jitted identity may return the caller's own
                # buffer, which the donation would delete.
                params, momentum = jax.device_put((params, momentum),
                                                  may_alias=False)
            out = self._train_step(params, xb, yb, momentum)
            self._returned = tuple(
                weakref.ref(a) for a in jax.tree_util.tree_leaves(out[:-1]))
        self.calls += 1
        self.donated_calls += donate
        return out

    def _handed_back(self, params, momentum) -> bool:
        """Is every leaf of ``params`` and ``momentum`` the live array, in
        its place, that the last :meth:`train_step` returned?"""
        leaves = jax.tree_util.tree_leaves((params, momentum))
        returned = self._returned
        return len(leaves) == len(returned) and all(
            r() is a and not a.is_deleted()
            for r, a in zip(returned, leaves))

    @functools.partial(jax.jit, static_argnums=0,
                       donate_argnames=("params", "momentum"))
    def _train_step(self, params, xb, yb, momentum=None):
        return self._step_impl(params, xb, yb, momentum)

    @functools.partial(jax.jit, static_argnums=0)
    def train_step_metrics(self, params, xb, yb, momentum=None, *,
                           step=None):
        """:meth:`train_step` with numerics telemetry: returns
        ``(step_outputs, taps)`` where ``step_outputs`` is exactly what
        ``train_step`` returns — bit-identical codes, the counters are
        pure reads — and ``taps`` maps ``"layer/op/counter"`` to int32
        counts (feed to ``MetricsRegistry.merge_numerics_taps`` with
        :meth:`lanes`).  Layers whose spec says ``metrics=off`` stay
        silent; ``metrics=full`` adds the Δ-LUT ``dhist`` shadow pass.
        Never donates: the caller's arrays survive the call.

        A model built with a :class:`~repro.resil.inject.FaultPlan` runs
        with it armed, keyed by ``step`` (a traced int32: the plan's
        ``[start, stop)`` window is data, so one graph serves every
        step), and raises ``ValueError`` without one.  The taps are
        computed *after* injection, so detectors see what the next layer
        sees.  Without a plan ``step`` is ignored.  The data-parallel
        model shares this method.
        """
        if self.fault_plan is not None and step is None:
            raise ValueError(
                "this model carries a FaultPlan: train_step_metrics needs "
                "step= (a traced int32) to key its faults")
        with _inj.injecting(self.fault_plan, step):
            with _obs.collecting() as col:
                out = self._step_impl(params, xb, yb, momentum)
                return out, col.taps()

    @functools.partial(jax.jit, static_argnums=0)
    def predict(self, params, xb):
        x = encode(xb, self.fmts["hidden"])
        _, _, z2 = self._forward(params, x)
        # signed argmax on LNS codes (no decode needed)
        key = jnp.where(z2.sign == 0, z2.code, -z2.code)
        big = jnp.int32(1 << 30)
        key = jnp.where(z2.sign == 0, key + big, key - big)
        return jnp.argmax(key, axis=-1)


BACKENDS = {"float": FloatMLP, "fxp": FxpMLP, "lns": LNSMLP}


def make_mlp(backend: str, cfg: MLPConfig):
    if cfg.data_parallel > 1 and backend != "lns":
        raise ValueError(
            f"data_parallel={cfg.data_parallel} is the LNS DP subsystem "
            f"(distributed/lns_dp); the {backend!r} backend has no "
            f"deterministic-reduce train step")
    if backend == "lns" and (cfg.data_parallel > 1
                             or cfg.spec.reduce.grad_segments):
        # Data-parallel LNS training with the deterministic ⊞ gradient
        # all-reduce (lazy import: distributed pulls in shard_map/mesh
        # machinery the single-device paths never need).  An explicit
        # grad_segments routes here even at data_parallel=1 so that
        # single- and multi-device runs sharing a canonical segmentation
        # are bit-identical through this public surface; the unsegmented
        # PR-1 LNSMLP remains the default when neither is set.
        from ..distributed.lns_dp import DPConfig, LNSDataParallelMLP
        dp = DPConfig(num_devices=cfg.data_parallel,
                      reduce=cfg.spec.reduce)
        return LNSDataParallelMLP(cfg, dp)
    return BACKENDS[backend](cfg)
