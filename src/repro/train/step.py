"""Train-step factory: loss → grads → optimizer update, with optional
microbatch gradient accumulation and log-domain gradient compression.

``make_train_step`` returns a pure function (state, batch) → (state,
metrics) suitable for jax.jit with in/out shardings from
distributed/sharding.py.  TrainState is a plain dict so shardings map
leaf-for-leaf.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..core.plan import NumericsPlan
from ..core.spec import NumericsSpec
from ..nn import Runtime, loss_fn
from ..nn.config import ModelConfig
from ..obs.trace import phase_scope
from ..optim import fake_compress_roundtrip, make_optimizer
from ..optim.optimizers import OptimizerConfig


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Execution config of the LM train step.

    Numerics axes (⊞-MAC backend, gradient-reduce semantics) belong to the
    model's :class:`~repro.core.spec.NumericsSpec` — set them in
    ``ModelConfig.numerics`` (``"lns16-train-emulate,backend=pallas"``,
    ``"bf16,reduce.mode=float-psum"``, …).  The loose ``matmul_backend=``
    and ``reduce_mode=`` keywords are the deprecated pre-spec spelling;
    they still work (folded into the spec by ``resolve_numerics``) with a
    ``DeprecationWarning``.
    """

    microbatches: int = 1            # gradient-accumulation splits
    grad_clip: float = 0.0           # global-norm clip; 0 = off
    compress_grads: bool = False     # log-int8 roundtrip + error feedback
    loss_dtype: str = "float32"
    matmul_backend: Optional[str] = None  # DEPRECATED → numerics spec
                                     # 'backend=' override
    data_parallel: int = 1           # devices on the 'data' mesh axis
    nan_guard: bool = False          # skip the update (params/opt state
                                     # unchanged, step still advances) when
                                     # loss or any grad is nonfinite;
                                     # metrics report 'update_skipped'
    reduce_mode: Optional[str] = None  # DEPRECATED → numerics spec
                                     # 'reduce.mode='.  None resolves to
                                     # the spec's reduce.mode; the LM path
                                     # supports 'float-psum' only (boxplus
                                     # is the paper-MLP DP subsystem —
                                     # see distributed/lns_dp.py)

    def __post_init__(self):
        legacy = [f"{k}={v!r}" for k, v in
                  (("matmul_backend", self.matmul_backend),
                   ("reduce_mode", self.reduce_mode)) if v is not None]
        if legacy:
            hints = []
            if self.matmul_backend is not None:
                hints.append(f"backend={self.matmul_backend}")
            if self.reduce_mode is not None:
                hints.append(f"reduce.mode={self.reduce_mode}")
            warnings.warn(
                f"TrainConfig({', '.join(legacy)}) is deprecated; append "
                f"the override to the numerics spec instead, e.g. "
                f"ModelConfig.numerics='<spec>,{','.join(hints)}'",
                DeprecationWarning, stacklevel=3)


def resolve_numerics(cfg: ModelConfig,
                     tc: "TrainConfig" = None) -> tuple[ModelConfig,
                                                        NumericsPlan]:
    """Fold TrainConfig's legacy numerics overrides into one resolved plan.

    Parses ``cfg.numerics`` (alias, spec string, alias + ``key=value``
    overrides, or a per-layer :class:`~repro.core.plan.NumericsPlan`
    string), applies ``tc.matmul_backend`` / ``tc.reduce_mode`` as typed
    overrides of the plan's *default* spec (invalid values raise with the
    valid-values list; per-layer rules re-apply on top), and returns
    ``(cfg with canonical numerics string, plan)``.  This replaces the old
    policy-name string surgery (``cfg.numerics.rsplit("-", 1)[0] + "-" +
    tc.matmul_backend``): the override is a dataclass-field update, so it
    works for *any* spec — no naming convention required.
    """
    plan = NumericsPlan.parse(cfg.numerics)
    if tc is not None and tc.matmul_backend is not None:
        if not plan.lns_grad:
            raise ValueError(
                f"the matmul-backend override requires an LNS end-to-end "
                f"training spec (quantize includes 'grads'), got "
                f"{cfg.numerics!r}")
        plan = plan.with_(backend=tc.matmul_backend)
    if tc is not None and tc.reduce_mode is not None:
        plan = plan.with_(**{"reduce.mode": tc.reduce_mode})
    return cfg.with_(numerics=str(plan)), plan


def init_train_state(params, opt_cfg: OptimizerConfig,
                     tc: TrainConfig = TrainConfig()):
    opt_init, _ = make_optimizer(opt_cfg)
    state = {"params": params, "opt": opt_init(params),
             "step": jnp.zeros((), jnp.int32)}
    if tc.compress_grads:
        state["residual"] = jax.tree.map(jnp.zeros_like, params)
    return state


def _split_batch(batch, n):
    return [jax.tree.map(lambda x: x[i::n], batch) for i in range(n)]


def _clip(grads, max_norm):
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / (gn + 1e-9))
    return jax.tree.map(lambda g: (g * scale.astype(g.dtype)), grads), gn


def make_train_step(cfg: ModelConfig, opt_cfg: OptimizerConfig,
                    rt: Runtime = Runtime(),
                    tc: TrainConfig = TrainConfig()):
    # One resolved spec decides every numerics axis (⊞-MAC backend,
    # reduce semantics); legacy TrainConfig overrides fold in here.  The
    # spec's ReduceSpec defaults to boxplus (the paper-MLP contract), but
    # the LM step always reduces float-psum — so only an *explicit*
    # boxplus request (a reduce.mode key in the numerics string, detected
    # by the parser's own tokenizer, or the deprecated knob) trips the
    # not-supported guard.  Best-effort by design: canonical spec strings
    # never carry alias-default fields, so a round-trip through str()
    # drops an explicit boxplus marker and skips this diagnostic — the
    # executed semantics are float-psum either way (the guard gates an
    # error message, never the arithmetic).
    default_seg = str(cfg.numerics).split(";", 1)[0]  # plan's default spec
    requested_boxplus = (
        tc.reduce_mode == "boxplus"
        or ("reduce.mode" in NumericsSpec.explicit_keys(default_seg)
            and NumericsPlan.parse(cfg.numerics).reduce.mode == "boxplus"))
    cfg, plan = resolve_numerics(cfg, tc)
    if requested_boxplus and tc.data_parallel > 1:
        # The LM step's gradients are float-view (custom_vjp boundary), so
        # only the linear psum semantics apply here; the deterministic
        # log-domain ⊞ schedule lives where gradients *are* LNS codes.
        raise NotImplementedError(
            "reduce.mode='boxplus' applies to the end-to-end LNS paper-MLP "
            "path (distributed/lns_dp.LNSDataParallelMLP / "
            "run_experiment(..., data_parallel=...)); the LM train step "
            "reduces float gradients — use reduce.mode='float-psum'")
    _, opt_update = make_optimizer(opt_cfg)
    # An MoE step also reports its expert layers' counters (rows routed to
    # each held expert, assignments dropped) among its metrics.
    with_stats = cfg.family == "moe"

    def grads_of(params, batch):
        if with_stats:
            (loss, stats), g = jax.value_and_grad(
                lambda p: loss_fn(p, batch, cfg, rt, with_stats=True),
                has_aux=True)(params)
            return loss, g, stats
        loss, g = jax.value_and_grad(lambda p: loss_fn(p, batch, cfg, rt))(
            params)
        return loss, g, {}

    def step(state, batch):
        params = state["params"]
        if tc.microbatches > 1:
            shards = _split_batch(batch, tc.microbatches)
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *shards)

            def acc_fn(carry, mb):
                loss_a, g_a = carry
                loss, g, _ = grads_of(params, mb)
                return (loss_a + loss,
                        jax.tree.map(jnp.add, g_a, g)), None

            zero = (jnp.zeros((), jnp.float32),
                    jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params))
            with phase_scope("grad"):
                (loss, grads), _ = jax.lax.scan(acc_fn, zero, stacked)
            inv = 1.0 / tc.microbatches
            loss = loss * inv
            grads = jax.tree.map(lambda g: g * inv, grads)
            stats = {}
        else:
            # One value_and_grad: forward and backward share this scope.
            with phase_scope("grad"):
                loss, grads, stats = grads_of(params, batch)
        metrics = {"loss": loss, **stats}
        if tc.grad_clip:
            grads, gn = _clip(grads, tc.grad_clip)
            metrics["grad_norm"] = gn
        if tc.compress_grads:
            grads, res = fake_compress_roundtrip(grads, state["residual"])
        with phase_scope("update"):
            new_params, new_opt = opt_update(params, grads, state["opt"],
                                             state["step"])
        if tc.nan_guard:
            # A nonfinite loss or gradient poisons params/opt state
            # irreversibly (momentum carries the NaN forward); drop the
            # whole update instead.  jnp.where keeps the step a single
            # traced graph — no host round-trip, works under pmap/shard_map.
            finite = jnp.isfinite(loss)
            for g in jax.tree.leaves(grads):
                finite = finite & jnp.all(jnp.isfinite(
                    g.astype(jnp.float32)))
            keep = lambda new, old: jax.tree.map(
                lambda n, o: jnp.where(finite, n, o), new, old)
            new_params = keep(new_params, params)
            new_opt = keep(new_opt, state["opt"])
            metrics["update_skipped"] = (~finite).astype(jnp.int32)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        if tc.compress_grads:
            new_state["residual"] = res
        return new_state, metrics

    return step
