"""Data-parallel LNS training with deterministic log-domain gradient reduce.

This subsystem scales the paper's end-to-end log-domain training step
(``paper/mlp.py: LNSMLP``) over a ``data`` mesh axis with ``shard_map``,
while keeping the ⊞ accumulation order — which in LNS arithmetic is part of
the *semantics*, not an implementation detail — a pure function of the
problem, never of the hardware layout.

The contract (see ``lns_reduce.py`` for the why):

* The global batch is cut into ``grad_segments`` canonical contiguous
  segments (fixed by config, not by device count); each device owns a
  contiguous run of segments.
* Backward-weight products are computed **per segment** on the kernel path
  (``LNSMatmulBackend.matmul_dw_partials`` — the dW Pallas kernel with
  partial-code flush), bias gradients per segment via sequential ⊞ folds.
* Cross-device combine = all-gather in segment order + a fixed-schedule ⊞
  fold (``reduce_mode="boxplus"``).  Training on any device count dividing
  ``grad_segments`` yields **bit-identical weight codes**, equal to the
  single-device ``reference_train_step`` running the same schedule without
  any collective.
* ``reduce_mode="float-psum"`` is the fast escape hatch: decode → psum →
  re-encode.  Cheaper on the wire, not bit-stable across device counts.

With ``grad_segments == global batch`` each segment is one sample, the
per-segment partial is the sample's exact outer product (⊞-fold of a single
term), and the sequential combine *is* the paper's sequential MAC over the
batch — i.e. the schedule degrades gracefully to PR 1's single-device
semantics.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..core.plan import NumericsPlan
from ..core.spec import ReduceSpec
from ..obs import metrics as _obs
from ..obs.trace import host_span, phase_scope
from ..paper.mlp import LNSMLP, MLPConfig, PARAM_LAYER
from ..resil import inject as _inj
from .lns_reduce import (combine_partials, deterministic_boxplus_allreduce,
                         float_psum_allreduce)


@dataclasses.dataclass(frozen=True)
class DPConfig:
    """Data-parallel execution config for the LNS train step.

    The reduction semantics live in one :class:`~repro.core.spec.ReduceSpec`
    (``mode`` / ``grad_segments`` / ``schedule``) — the same object a
    :class:`~repro.core.spec.NumericsSpec` carries, so a DP plan is derived
    from a spec with :meth:`from_spec` (or ``runtime.dp_config``) and the
    reduce axis is configured in exactly one place.

    ``reduce.grad_segments`` fixes the canonical segmentation of the global
    batch.  Bit-identical results across device counts hold for any set of
    runs sharing the same ``grad_segments`` (every count must divide it);
    ``0`` resolves to ``num_devices``, which keeps same-count runs
    deterministic but ties the schedule to the device count — pass an
    explicit value when comparing different counts.

    The legacy loose knobs (``reduce_mode=`` / ``grad_segments=`` /
    ``reduce_schedule=``) are still accepted as constructor keywords and
    fold into ``reduce``; the same names read back as properties.
    """

    num_devices: int = 1
    reduce: ReduceSpec = ReduceSpec()
    axis_name: str = "data"
    reduce_with_kernel: bool | None = None  # None → (backend == 'pallas')
    # legacy loose knobs, folded into ``reduce`` (None → keep spec value)
    reduce_mode: dataclasses.InitVar["str | None"] = None
    grad_segments: dataclasses.InitVar["int | None"] = None
    reduce_schedule: dataclasses.InitVar["str | None"] = None

    def __post_init__(self, reduce_mode, grad_segments, reduce_schedule):
        legacy = {k: v for k, v in (("mode", reduce_mode),
                                    ("grad_segments", grad_segments),
                                    ("schedule", reduce_schedule))
                  if v is not None}
        if legacy:
            # ReduceSpec validation raises with the valid-values list.
            object.__setattr__(self, "reduce", self.reduce.with_(**legacy))
        if self.num_devices < 1:
            raise ValueError(f"num_devices must be >= 1, got "
                             f"{self.num_devices}")

    @classmethod
    def from_spec(cls, spec: "NumericsSpec | NumericsPlan | str",
                  num_devices: int = 1, **kw) -> "DPConfig":
        """The DP plan a :class:`NumericsSpec` (or plan) describes.

        The reduce axis lives on the plan's *default* spec: the canonical
        segmentation of the global batch is one global contract (the
        schedule must be a pure function of the problem), while the ⊞
        combine of each parameter's partials runs in that parameter's own
        layer format — see ``LNSDataParallelMLP.train_step``.
        """
        return cls(num_devices=num_devices,
                   reduce=NumericsPlan.parse(spec).reduce, **kw)

    def segments(self, global_batch: int) -> int:
        s = self.reduce.grad_segments or self.num_devices
        if s % self.num_devices:
            raise ValueError(
                f"grad_segments={s} not divisible by "
                f"num_devices={self.num_devices}")
        if global_batch % s:
            raise ValueError(
                f"global batch {global_batch} not divisible into {s} "
                f"canonical segments")
        return s


# Legacy read access: cfg.reduce_mode etc. keep working as views over the
# nested ReduceSpec.  (Assigned post-class: the names double as InitVar
# constructor keywords above.)
DPConfig.reduce_mode = property(lambda self: self.reduce.mode)
DPConfig.grad_segments = property(lambda self: self.reduce.grad_segments)
DPConfig.reduce_schedule = property(lambda self: self.reduce.schedule)


def make_data_mesh(num_devices: int, axis_name: str = "data") -> Mesh:
    """1-D mesh over the first ``num_devices`` local devices."""
    devs = jax.devices()
    if num_devices > len(devs):
        raise ValueError(
            f"requested data_parallel={num_devices} but only "
            f"{len(devs)} devices are attached (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N to emulate "
            f"more on CPU)")
    return Mesh(np.array(devs[:num_devices]), (axis_name,))


class LNSDataParallelMLP:
    """Drop-in ``make_mlp``-style model running the DP LNS train step.

    Exposes the same ``init`` / ``train_step`` / ``predict`` surface as
    :class:`~repro.paper.mlp.LNSMLP`, so ``paper/training.run_experiment``
    drives it unchanged.  ``train_step`` shards the batch over the ``data``
    mesh axis and reduces weight-gradient partials with the deterministic
    ⊞ schedule (or float psum, per ``DPConfig.reduce_mode``).

    Under a per-layer :class:`~repro.core.plan.NumericsPlan` the reduce
    plan is *per parameter*: each parameter's per-segment partials are
    LNS codes in that parameter's own layer format, so the all-gather +
    fixed-schedule ⊞ fold runs under that layer's Δ engine (and its
    backend's kernel/interpret mode).  The segmentation itself stays one
    global contract, so the 1/2/4-device bit-identical invariance holds
    under mixed formats too — device count still only changes *where* a
    segment partial is computed, never which arithmetic combines it.

    With ``cfg.momentum > 0`` the step threads a replicated ⊞-momentum
    pytree: the momentum update runs *after* the deterministic reduce on
    the already-replicated gradients, so it inherits the invariance.

    With ``cfg.fused`` (default) the parameter update runs through the
    one-pass fused-update kernel (``LNSMatmulBackend.fused_update`` via
    ``LNSMLP.apply_updates``) — the fused epilogue applies strictly
    *after* the canonical ⊞-combine, on the replicated gradients, so the
    reduction-order contract (and the 1/2/4-device bit-identical weight
    codes) is untouched; the kernel itself is bit-identical to the
    unfused ``apply_update`` composition.
    """

    def __init__(self, cfg, dp: DPConfig):
        self.cfg = cfg
        self.dp = dp
        self.inner = LNSMLP(cfg)
        self.fault_plan = self.inner.fault_plan
        self.mesh = make_data_mesh(dp.num_devices, dp.axis_name)

    # -- passthroughs ----------------------------------------------------
    def init(self, key):
        return self.inner.init(key)

    def init_momentum(self, params):
        return self.inner.init_momentum(params)

    def predict(self, params, xb):
        return self.inner.predict(params, xb)

    def _use_kernel(self, param: str) -> bool:
        if self.dp.reduce_with_kernel is not None:
            return self.dp.reduce_with_kernel
        return self.inner.param_runtimes[param].spec.backend == "pallas"

    # -- the DP step -----------------------------------------------------
    def _step_impl(self, params, xb, yb, momentum=None):
        inner, dp = self.inner, self.dp
        segments = dp.segments(xb.shape[0])
        segs_local = segments // dp.num_devices
        axis = dp.axis_name
        # Fault wiring (resil/inject), all no-ops without an ambient plan:
        # weight-code flips apply here on the replicated params (the
        # outer trace owns the step tracer); segment-partial faults apply
        # *inside* the mapped body with the plan captured statically and
        # the global slot recovered from lax.axis_index — the outer step
        # tracer must not cross into the per-device trace (the same
        # tracer-leak discipline that suspends obs collection below).
        fplan = _inj.active_plan()
        params = _inj.inject_param_codes(params,
                                         param_fmts=inner.param_fmts,
                                         param_layer=PARAM_LAYER)

        collect = _obs.enabled()

        def local_fn(params, momentum, xb_l, yb_l):
            grads, loss = inner.per_segment_grads(params, xb_l, yb_l,
                                                  segs_local)
            if fplan is not None:
                grads = _inj.inject_segment_partials(
                    grads, param_fmts=inner.param_fmts,
                    param_layer=PARAM_LAYER, segs_local=segs_local,
                    axis_name=axis, plan=fplan)
            # Format-correct ⊞-allreduce per parameter: each leaf's
            # partials combine under its own layer's Δ engine.
            red = {}
            for k, g in grads.items():
                eng = inner.param_engines[k]
                if dp.reduce.mode == "boxplus":
                    # The combine's fold shape follows the parameter's
                    # own layer spec's `blocks` axis (auto = autotuned
                    # op="boxsum" entries) — tiling-invariant, so the
                    # canonical-schedule contract is untouched.
                    red[k] = deterministic_boxplus_allreduce(
                        g, axis_name=axis, eng=eng,
                        schedule=dp.reduce.schedule,
                        use_kernel=self._use_kernel(k),
                        interpret=inner.param_runtimes[k].matmul._interp(),
                        blocks=inner.param_runtimes[k].spec.blocks)
                else:
                    red[k] = float_psum_allreduce(g, axis_name=axis,
                                                  eng=eng)
            # The update runs after the combine, on the replicated
            # gradients, identically on every device: a Pallas kernel
            # outside shard_map cannot be partitioned over the mesh.  Its
            # taps leave the body as replicated outputs.
            with phase_scope("update"):
                if collect:
                    with _obs.collecting() as col:
                        new_p, new_m = inner.apply_updates(params, red,
                                                           momentum)
                    taps = col.taps()
                else:
                    new_p, new_m = inner.apply_updates(params, red,
                                                       momentum)
                    taps = {}
            return new_p, new_m, red, jax.lax.pmean(loss, axis), taps

        mapped = jax.shard_map(
            local_fn, mesh=self.mesh,
            in_specs=(P(), P(), P(axis), P(axis)),
            out_specs=(P(), P(), P(), P(), P()),
            check_vma=False)
        # Taps must not fire inside the shard_map body (the per-device
        # trace's values would leak onto the Python-side collector), so
        # collection is suspended across the mapped call; the combined
        # gradients are observed below on the replicated values — the DP
        # canonical-reduce schedule itself is untouched.
        with phase_scope("reduce"), _obs.suspended(), _inj.suspended():
            new_params, momentum, grads, loss, taps = mapped(
                params, momentum, xb, yb)
        if collect:
            for k, g in grads.items():
                layer = PARAM_LAYER[k]
                if inner.metrics_levels[layer] != "off":
                    _obs.observe_codes(g, inner.param_fmts[k], layer=layer,
                                       op=f"dp_grad.{k}")
            _obs.add_taps(taps)
        if momentum is None:
            return new_params, loss
        return new_params, momentum, loss

    @host_span("repro.train_step")
    @functools.partial(jax.jit, static_argnums=0)
    def train_step(self, params, xb, yb, momentum=None):
        """Plain DP step — no collector, telemetry gates statically off,
        jitted graph unchanged from the pre-obs subsystem."""
        return self._step_impl(params, xb, yb, momentum)

    # Per-leaf combined-gradient health (``dp_grad.*``) plus the update
    # epilogue taps from ``inner.apply_updates``; in-shard_map compute
    # reports nothing (collection is suspended there by construction).
    train_step_metrics = LNSMLP.train_step_metrics


def reference_train_step(inner, params, xb, yb, *, grad_segments: int,
                         reduce_schedule: str = "sequential",
                         momentum=None):
    """Single-device sequential baseline of the canonical DP schedule.

    Runs the identical segmented backward + fixed-schedule ⊞ combine on one
    device with no mesh, no shard_map, and no collectives.  The DP step
    must reproduce its weight codes bit-exactly at every device count
    dividing ``grad_segments`` — this is the anchor the invariance tests
    compare against.  Pass a momentum pytree (``inner.init_momentum``) to
    run the ⊞-momentum update; the return then gains the new momentum:
    ``(params, momentum, loss)``.
    """
    grads, loss = inner.per_segment_grads(params, xb, yb, grad_segments)
    grads = {k: combine_partials(g, inner.param_engines[k],
                                 schedule=reduce_schedule)
             for k, g in grads.items()}
    new_params, momentum = inner.apply_updates(params, grads, momentum)
    if momentum is None:
        return new_params, loss
    return new_params, momentum, loss


def run_device_count_invariance_check(device_counts=(1, 2, 4), *,
                                      steps: int = 3, batch: int = 8,
                                      numerics=None,
                                      momentum: float = 0.0,
                                      fused: bool = True,
                                      n_in: int = 12, n_hidden: int = 9,
                                      n_out: int = 4,
                                      grad_segments=None,
                                      matmul_backend=None,
                                      reduce_mode=None,
                                      seed: int = 0, verbose: bool = False):
    """Train the paper MLP at several device counts; compare weight codes.

    ``numerics`` is the unified descriptor — a spec string, or a
    :class:`~repro.core.plan.NumericsPlan` string with per-layer rules
    (``"lns16-train-pallas,reduce.grad_segments=4;hidden=fmt:lns12"``);
    its ``reduce.grad_segments`` fixes the canonical segmentation
    (default 4).  ``fused`` toggles the fused post-combine update kernel
    (default on, matching ``MLPConfig.fused``); invariance must hold
    either way.  The loose ``grad_segments=`` / ``matmul_backend=`` /
    ``reduce_mode=`` keywords are the deprecated pre-spec spelling and
    fold into the descriptor with a ``DeprecationWarning``.

    Returns ``(ok, runs)`` where ``ok`` is True iff every device count
    produced weight codes bit-identical to ``reference_train_step``.  Used
    by tests (in-process when enough devices are attached, via a
    subprocess with ``--xla_force_host_platform_device_count`` otherwise)
    and by ``examples/train_data_parallel.py``.
    """
    legacy = {k: v for k, v in (("backend", matmul_backend),
                                ("reduce.mode", reduce_mode),
                                ("reduce.grad_segments", grad_segments))
              if v is not None}
    if numerics is None:
        numerics = "lns16-train-pallas,reduce.grad_segments=4"
    plan = NumericsPlan.parse(numerics)
    if legacy:
        plan = plan.with_(**legacy)
        warnings.warn(
            f"run_device_count_invariance_check(matmul_backend=/"
            f"reduce_mode=/grad_segments=) are deprecated; pass the "
            f"unified descriptor instead: numerics={str(plan)!r}",
            DeprecationWarning, stacklevel=2)
    segs = plan.reduce.grad_segments or 4
    mode = plan.reduce.mode

    rng = np.random.default_rng(seed)
    xb = rng.uniform(0, 1, size=(batch, n_in)).astype(np.float32)
    yb = rng.integers(0, n_out, size=(batch,))
    # The model config carries grad_segments=0 so the single-device
    # reference LNSMLP below stays the plain (unrouted) model; the DP
    # plan re-derives the canonical segmentation from ``plan``.
    cfg = MLPConfig(n_in=n_in, n_hidden=n_hidden, n_out=n_out,
                    spec=plan.with_(**{"reduce.grad_segments": 0}),
                    momentum=momentum, fused=fused)

    inner = LNSMLP(cfg)
    ref_params = inner.init(jax.random.PRNGKey(seed))
    ref_mom = inner.init_momentum(ref_params)
    for _ in range(steps):
        out = reference_train_step(
            inner, ref_params, xb, yb, grad_segments=segs,
            momentum=ref_mom)
        if ref_mom is None:
            ref_params, _ = out
        else:
            ref_params, ref_mom, _ = out

    runs, ok = {}, True
    for d in device_counts:
        dp = DPConfig.from_spec(plan.with_(
            **{"reduce.grad_segments": segs}), num_devices=d)
        model = LNSDataParallelMLP(cfg, dp)
        params = model.init(jax.random.PRNGKey(seed))
        mom = model.init_momentum(params)
        for _ in range(steps):
            out = model.train_step(params, xb, yb, mom)
            if mom is None:
                params, loss = out
            else:
                params, mom, loss = out
        same = all(
            bool(np.array_equal(np.asarray(params[k].code),
                                np.asarray(ref_params[k].code))
                 and np.array_equal(np.asarray(params[k].sign),
                                    np.asarray(ref_params[k].sign)))
            for k in ref_params)
        runs[d] = dict(params=params, loss=float(loss),
                       matches_reference=same)
        ok = ok and (same if mode == "boxplus" else True)
        if verbose:
            print(f"[lns_dp] devices={d} loss={float(loss):.4f} "
                  f"bit-identical-to-reference={same}")
    return ok, runs
