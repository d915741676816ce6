"""Training harness for the paper-reproduction experiments (Sec. 5)."""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import numpy as np

from . import datasets
from .mlp import MLPConfig, make_mlp

# Paper Sec. 5: weight decay "optimized for each individual dataset"; the
# 12-bit runs needed larger regularization.  These are our tuned values
# (applied every 16 steps — see FxpMLP.apply_decay).
WEIGHT_DECAY = {16: 0.01, 12: 0.3}


@dataclasses.dataclass
class RunResult:
    backend: str
    dataset: str
    bits: int
    approx: str
    val_curve: list
    test_acc: float
    seconds: float
    losses: list = dataclasses.field(default_factory=list)  # per step
    params: Any = None        # the trained parameters
    lanes: dict = dataclasses.field(default_factory=dict)  # layer → lane

    def row(self):
        return dict(backend=self.backend, dataset=self.dataset,
                    bits=self.bits, approx=self.approx,
                    test_acc=self.test_acc, val_curve=self.val_curve,
                    seconds=self.seconds)


def evaluate(model, params, x, y, batch: int = 500) -> float:
    correct = 0
    for i in range(0, len(x), batch):
        pred = np.asarray(model.predict(params, x[i:i + batch]))
        correct += int((pred == y[i:i + batch]).sum())
    return correct / len(x)


def run_experiment(backend: str, dataset: str, *, bits: int = 16,
                   approx: str = "lut", epochs: int = 5,
                   batch_size: int = 5, lr: float = 0.01,
                   weight_decay: float | None = None,
                   momentum: float = 0.0, seed: int = 0,
                   data_dir: str = "data", stochastic_round: bool = False,
                   numerics=None,
                   matmul_backend: str | None = None,
                   data_parallel: int = 1,
                   reduce_mode: str | None = None,
                   grad_segments: int | None = None,
                   max_steps_per_epoch: int | None = None) -> RunResult:
    """Train the paper MLP with one backend; returns learning curve + acc.

    Paper hyperparameters: SGD, minibatch 5, lr 0.01, 20 epochs, 1:5
    validation holdout.  ``epochs``/dataset size are reduced by default to
    fit this container's CPU budget (the LNS path emulates every ⊞ in
    integer ops); pass epochs=20 and real IDX data for the full protocol.

    ``numerics`` (lns backend only) is the unified arithmetic descriptor —
    a :class:`~repro.core.spec.NumericsSpec`, a per-layer
    :class:`~repro.core.plan.NumericsPlan`, or their string forms:
    ``"lns16-train-pallas"``,
    ``"lns16-train-emulate,reduce.mode=float-psum,reduce.grad_segments=4"``,
    or a mixed-format plan such as
    ``"lns16-train-pallas;hidden=fmt:lns12"`` (hidden layer in lns12,
    softmax-critical output layer in lns16).  It selects the ⊞-MAC
    execution backend per layer (``backend=emulate|pallas``, bit-identical
    weight trajectories) and, with ``data_parallel > 1``, the
    gradient-reduce semantics: ``reduce.mode=boxplus`` is the
    deterministic ⊞ all-reduce (bit-stable across device counts sharing
    ``reduce.grad_segments`` — also under mixed formats, where each
    parameter reduces in its own layer's arithmetic), ``float-psum`` the
    fast escape hatch.  ``batch_size`` must divide into the canonical
    segment count (``grad_segments`` or ``data_parallel``).
    ``momentum`` (lns backend only) enables the pure-LNS ⊞-momentum
    update; the harness threads the replicated momentum state through the
    step.  The loose ``matmul_backend=`` / ``reduce_mode=`` /
    ``grad_segments=`` keywords are the deprecated pre-spec spelling
    (forwarded to ``MLPConfig``, which warns).
    """
    x, yl, x_te, y_te, spec = datasets.load(dataset, data_dir, seed)
    x_tr, y_tr, x_val, y_val = datasets.train_val_split(x, yl, 5, seed)
    wd = WEIGHT_DECAY[bits] if weight_decay is None else weight_decay
    legacy = {k: v for k, v in (("matmul_backend", matmul_backend),
                                ("reduce_mode", reduce_mode),
                                ("grad_segments", grad_segments))
              if v is not None}
    if momentum and backend != "lns":
        raise ValueError(
            f"momentum={momentum} is the pure-LNS ⊞-momentum update "
            f"(core/sgd.py); the {backend!r} backend does not implement it")
    cfg = MLPConfig(n_out=spec.n_classes, lr=lr, weight_decay=wd,
                    momentum=momentum, bits=bits, approx=approx,
                    stochastic_round=stochastic_round,
                    spec=numerics, data_parallel=data_parallel, **legacy)
    model = make_mlp(backend, cfg)
    params = model.init(jax.random.PRNGKey(seed))
    mom = model.init_momentum(params) \
        if momentum and hasattr(model, "init_momentum") else None

    rng = np.random.default_rng(seed)
    t0 = time.time()
    curve = []
    gstep = 0
    losses = []
    for _ in range(epochs):
        order = rng.permutation(len(x_tr))
        steps = len(order) // batch_size
        if max_steps_per_epoch is not None:
            steps = min(steps, max_steps_per_epoch)
        for s in range(steps):
            with jax.profiler.StepTraceAnnotation("repro.train",
                                                  step_num=gstep):
                sl = order[s * batch_size:(s + 1) * batch_size]
                if stochastic_round and backend == "fxp":
                    params, loss = model.train_step(
                        params, x_tr[sl], y_tr[sl],
                        jax.random.PRNGKey(seed * 1_000_003 + gstep))
                elif mom is not None:
                    params, mom, loss = model.train_step(params, x_tr[sl],
                                                         y_tr[sl], mom)
                else:
                    params, loss = model.train_step(params, x_tr[sl], y_tr[sl])
                losses.append(loss)
                gstep += 1
                if hasattr(model, "apply_decay") and wd and (s + 1) % 16 == 0:
                    params = model.apply_decay(params, 16)
        curve.append(evaluate(model, params, x_val, y_val))
    test = evaluate(model, params, x_te, y_te)
    inner = getattr(model, "inner", model)
    lanes = inner.lanes() if hasattr(inner, "lanes") else {}
    return RunResult(backend, dataset, bits, approx, curve, test,
                     time.time() - t0, [float(v) for v in losses], params,
                     lanes)
