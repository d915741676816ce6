"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

Wires data pipeline → train step → checkpoint manager with fault-tolerant
restart.  On this container it runs reduced configs on the host mesh; on a
real cluster the same driver runs the full config on the production mesh
(jax.distributed.initialize is a no-op here).

Fault tolerance drill: kill the process mid-run and relaunch with the same
--ckpt-dir — it resumes from the latest atomic checkpoint at the exact
batch index (deterministic data-by-step).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, reduced
from .compile_cache import enable_compile_cache
from ..ckpt import CheckpointManager
from ..core.plan import NumericsPlan
from ..data import DataConfig, SyntheticLMDataset
from ..nn import Runtime, init_params
from ..nn.config import ShapeCell
from ..obs import JsonlSink, MetricsRegistry, StepTimer, maybe_profile
from ..obs import metrics as _obs
from ..optim.optimizers import AdamWConfig, SGDConfig
from ..train import TrainConfig, init_train_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", choices=["adamw", "sgd"], default="adamw")
    ap.add_argument("--numerics", default="bf16",
                    help="a NumericsSpec alias (bf16 | fp32 | lns16-qat | "
                    "lns12-qat | lns16-exact | lns16-train-{emulate,pallas} "
                    "| ...) optionally followed by key=value overrides, "
                    "e.g. 'lns16-train-pallas,reduce.mode=boxplus', or a "
                    "per-layer NumericsPlan string with ';'-separated "
                    "<pattern>=<key>:<value> rules, e.g. "
                    "'bf16;layers.mlp=fmt:lns16,delta:lut20,"
                    "quantize:params'")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--data-parallel", type=int, default=1,
                    help="devices on the 'data' mesh axis (batch must "
                    "divide; emulate extra CPU devices with XLA_FLAGS="
                    "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--reduce-mode", default=None,
                    choices=["float-psum", "boxplus"],
                    help="gradient all-reduce semantics; 'boxplus' is the "
                    "paper-MLP DP path (repro.distributed.lns_dp), the LM "
                    "step uses float-psum.  Default: whatever the "
                    "--numerics spec says (reduce.mode=...), else "
                    "float-psum")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="write per-step numerics + timing telemetry as "
                    "JSONL (loss, step_time_ms, per-layer saturation/"
                    "zero-rate counters).  Uses a separate metrics-enabled "
                    "jitted step; weight codes stay bit-identical to a "
                    "run without --metrics")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="dump a jax.profiler trace of the training loop "
                    "there; each iteration is a 'repro.train' step in it")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-numerics-mismatch", action="store_true",
                    help="restore a checkpoint whose stamped numerics "
                    "plan differs from --numerics (deliberate format "
                    "migration; LNS codes are NOT re-encoded)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    # Fold an explicit CLI --reduce-mode into the numerics string's
    # *default-spec* segment (an explicit flag wins over a reduce.mode
    # inside --numerics: later key=value tokens override earlier ones;
    # per-layer ';' rules are untouched).  The string is validated here,
    # so a bad alias/override/pattern fails before any compilation, and
    # kept as written (not canonicalized) so an explicit
    # reduce.mode=boxplus — which canonicalization would strip as an
    # alias default — still reaches make_train_step's supported-modes
    # guard.
    head, *rules = args.numerics.split(";")
    if args.reduce_mode is not None:
        head += f",reduce.mode={args.reduce_mode}"
    numerics = ";".join([head] + rules)
    plan = NumericsPlan.parse(numerics)
    cfg = cfg.with_(numerics=numerics,
                    remat="none" if args.reduced else "block")
    # Dead-pattern check up front too: parse only validates syntax and
    # vocabulary; a pattern matching none of this arch's layer paths
    # would otherwise surface mid-trace of the first step.
    from ..nn.model import known_layer_paths
    plan.validate_paths(known_layer_paths(cfg))
    print(f"[train] numerics spec: {plan}")
    cell = ShapeCell("train_cli", args.seq, args.batch, "train")

    opt = (AdamWConfig(lr=args.lr) if args.optimizer == "adamw"
           else SGDConfig(lr=args.lr, momentum=0.9))
    tc = TrainConfig(microbatches=args.microbatches, grad_clip=1.0,
                     compress_grads=args.compress_grads,
                     data_parallel=args.data_parallel)
    rt = Runtime()   # host mesh; production path goes through dryrun specs

    batch_sharding = state_sharding = None
    if args.data_parallel > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..distributed.lns_dp import make_data_mesh
        if args.batch % args.data_parallel:
            raise SystemExit(f"--batch {args.batch} not divisible by "
                             f"--data-parallel {args.data_parallel}")
        mesh = make_data_mesh(args.data_parallel)
        batch_sharding = NamedSharding(mesh, P("data"))
        state_sharding = NamedSharding(mesh, P())
        from ..core.spec import NumericsSpec
        eff_mode = (plan.reduce.mode
                    if "reduce.mode" in NumericsSpec.explicit_keys(head)
                    else "float-psum")
        print(f"[train] data-parallel over {args.data_parallel} devices "
              f"(reduce.mode={eff_mode}; XLA inserts the gradient "
              f"all-reduce)")

    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    state = init_train_state(params, opt, tc)
    # Checkpoints are stamped with the canonical plan string; a restore
    # under a different arithmetic fails unless explicitly allowed.
    mgr = CheckpointManager(
        args.ckpt_dir, numerics=plan,
        allow_numerics_mismatch=args.allow_numerics_mismatch) \
        if args.ckpt_dir else None
    start = 0
    if mgr is not None:
        restored, step0 = mgr.restore_latest(jax.eval_shape(lambda: state))
        if restored is not None:
            state, start = restored, int(step0)
            print(f"[train] resumed from step {start}")

    ds = SyntheticLMDataset(cfg, cell, DataConfig(seed=args.seed))
    base_step = make_train_step(cfg, opt, rt, tc)
    if args.metrics:
        # Metrics lane: a SEPARATE jitted entry point that wraps the same
        # unjitted step in a collector and observes the *updated* params
        # per leaf, outside the grad region (observer-only, so weight
        # codes are bit-identical to the plain step — tests/test_obs.py
        # pins that for the paper MLP; here the step body is shared).
        from jax.tree_util import tree_flatten_with_path
        known = known_layer_paths(cfg)

        def _leaf_layer(path):
            parts = [str(getattr(k, "key", k)) for k in path]
            dotted = ".".join(parts)
            best = ""
            for kp in known:
                if ((dotted == kp or dotted.startswith(kp + "."))
                        and len(kp) > len(best)):
                    best = kp
            return best or parts[0]

        def metrics_step(state, batch):
            with _obs.collecting() as col:
                state2, metrics = base_step(state, batch)
                for path, leaf in tree_flatten_with_path(
                        state2["params"])[0]:
                    layer = _leaf_layer(path)
                    spec = plan.resolve(layer)
                    if spec.metrics == "off" or spec.fmt is None:
                        continue
                    name = str(getattr(path[-1], "key", "param"))
                    _obs.observe_float(leaf, spec.fmt, layer=layer,
                                       op=f"param.{name}")
                return state2, metrics, col.taps()

        step_fn = jax.jit(metrics_step, donate_argnums=0)
        registry = MetricsRegistry(base_labels={
            "component": "train", "arch": args.arch, "spec": str(plan)})
        lanes = {p: plan.runtime_for(p).lane for p in known}
        sink = JsonlSink(args.metrics)
    else:
        step_fn = jax.jit(base_step, donate_argnums=0)
        registry = sink = None
    timer = StepTimer()
    if state_sharding is not None:
        state = jax.device_put(state, state_sharding)

    t0 = time.time()
    losses = []
    with maybe_profile(args.profile_dir):
        for step in range(start, args.steps):
            with jax.profiler.StepTraceAnnotation("repro.train",
                                                  step_num=step):
                batch = {k: jnp.asarray(v)
                         for k, v in ds.batch_at(step).items()}
                if batch_sharding is not None:
                    batch = jax.device_put(batch, batch_sharding)
                with timer.span("train.step"):
                    if sink is not None:
                        state, metrics, taps = step_fn(state, batch)
                    else:
                        state, metrics = step_fn(state, batch)
                    losses.append(float(metrics["loss"]))  # blocks on device
                if sink is not None:
                    registry.merge_numerics_taps(
                        jax.device_get(taps), lanes=lanes)
                    sink.write(registry.rows(reset=True), step=step + 1,
                               loss=losses[-1],
                               step_time_ms=timer.last("train.step"))
                if (step + 1) % args.log_every == 0 or step == args.steps - 1:
                    dt = (time.time() - t0) / max(len(losses), 1)
                    print(f"[train] step {step + 1}/{args.steps} "
                          f"loss {losses[-1]:.4f} ({dt * 1e3:.0f} ms/step)")
                if mgr is not None and (step + 1) % args.ckpt_every == 0:
                    mgr.save(step + 1, state, blocking=False)
    if mgr is not None:
        mgr.save(args.steps, state, blocking=True)
    if sink is not None:
        summary = timer.summary(skip_first=1)["train.step"]
        sink.write_row({"kind": "summary", "name": "train.step_time_ms",
                        **summary, "arch": args.arch, "spec": str(plan),
                        "steps": len(losses), "final_loss": losses[-1]})
        sink.close()
        print(f"[train] metrics written to {args.metrics} "
              f"(mean step {summary['mean_ms']:.1f} ms)")
    print(f"[train] done: first loss {losses[0]:.4f} → last "
          f"{losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    main()
