"""Smoke test of the log-domain training path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the deterministic ⊞-allreduce only

One chip runs four phases through the normal entry points:

* ``mlp``: the paper MLP (784-100-10, batch 64) trained ``STEPS`` steps
  by ``repro.paper.run_experiment`` on ``lns16-train-pallas`` and again on
  ``lns16-train-emulate``; the weight codes must agree bit for bit.
* ``kernels-qwen3``: the forward, dX and dW ⊞-MAC kernels at the
  qwen3-1.7b MLP width (d_model 2048, d_ff 6144) on the qwen3 phase's
  ``BATCH * SEQ`` rows, for every Δ the compiled lane takes (``lut20``,
  ``lut640``, ``bitshift``), bit for bit against the emulated sequential
  MAC on 16 rows spread over the output; each kernel's second call is
  timed on the host clock.
* ``kernels-gmm``: the grouped forward, dX and dW ⊞-MAC kernels at the
  deepseek-v2-lite expert width (d_model 2048, d_expert 1408, 16 experts,
  a bound of 6,144 rows with 1,536 routed: groups uneven, one empty, four
  over a 128-row tile), bit for bit against one plain kernel launch per
  expert on that expert's rows (lut20); each grouped kernel's second call
  is timed on the host clock.
* ``qwen3``: two train steps of qwen3-1.7b at its published widths, depth
  cut to ``LAYERS``, ``BATCH`` x ``SEQ`` tokens, through
  ``repro.train.make_train_step`` on
  ``lns16-train-pallas`` with the vocabulary head in float32; the losses
  must be finite and the first near the float32 model's.

``--chips 4`` runs only ``LNSDataParallelMLP`` at the paper MLP's width on
a 4-device ``data`` mesh, whose weight codes must equal the one-device
``reference_train_step`` bit for bit.

Every ⊞-MAC layer must report the ``pallas-hw`` lane.  Compile time is
measured apart from step time.  Earlier lines say what was found; the last
line is one JSON object, ``{"ok": true, "device": {...}}``, printed only
when every phase passed.  Anything but a TPU is refused.  Everything runs
in this one process.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: Paper-MLP train steps per run; the seed of every random input and weight.
STEPS, SEED = 3, 0
#: qwen3-1.7b depth, and the token batch (sequences x tokens) of its steps.
LAYERS, BATCH, SEQ = 2, 4, 128
#: The Δ kinds the compiled ⊞-MAC lane takes, by their plan names.
KERNEL_DELTAS = ("lut20", "lut640", "bitshift")

#: The qwen3 phase's plan: the ⊞-MAC training arithmetic everywhere but the
#: 151,936-wide vocabulary head, which runs in float32.
QWEN3_PLAN = "lns16-train-pallas;head=delta:none,fmt:none,quantize:none"


class PhaseFailed(Exception):
    pass


def check(cond: bool, what: str):
    if not cond:
        raise PhaseFailed(what)


class CompileClock:
    """Seconds JAX spent lowering and compiling programs, from its own
    monitoring events (tracing is left out: its events nest), and how
    many programs the persistent compile cache supplied or missed."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1


def say(phase: str, **kv):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def same_codes(a: dict, b: dict) -> bool:
    import numpy as np
    return all(np.array_equal(np.asarray(a[k].code), np.asarray(b[k].code))
               and np.array_equal(np.asarray(a[k].sign),
                                  np.asarray(b[k].sign)) for k in a)


def phase_mlp(clock):
    from repro.paper import run_experiment
    runs = {}
    for numerics in ("lns16-train-pallas", "lns16-train-emulate"):
        c0, t0 = clock.seconds, time.perf_counter()
        r = run_experiment("lns", "mnist", epochs=1, batch_size=64,
                           max_steps_per_epoch=STEPS,
                           numerics=numerics, seed=SEED)
        wall = time.perf_counter() - t0
        compile_s = clock.seconds - c0
        say("mlp", numerics=numerics, steps=len(r.losses),
            losses=[round(v, 6) for v in r.losses], lanes=r.lanes,
            compile_s=round(compile_s, 3),
            other_s=round(wall - compile_s, 3))
        check(len(r.losses) == STEPS, f"{numerics}: ran "
              f"{len(r.losses)} of {STEPS} steps")
        check(all(math.isfinite(v) for v in r.losses),
              f"{numerics}: nonfinite loss {r.losses}")
        runs[numerics] = r
    lanes = runs["lns16-train-pallas"].lanes
    check(lanes and all(v == "pallas-hw" for v in lanes.values()),
          f"MLP layers not on the compiled kernels: {lanes}")
    equal = same_codes(runs["lns16-train-pallas"].params,
                       runs["lns16-train-emulate"].params)
    say("mlp", weight_codes_pallas_eq_emulate=equal)
    check(equal, "pallas and emulate weight codes differ")


def phase_kernels_qwen3(clock):
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.core import LNS16, encode
    from repro.core.arithmetic import lns_matmul
    from repro.core.delta import DeltaEngine
    from repro.core.spec import DELTA_NAMES
    from repro.kernels import (lns_matmul_dw_kernel, lns_matmul_dx_kernel,
                               lns_matmul_kernel)
    cfg = get_config("qwen3-1.7b")
    rng = np.random.default_rng(SEED)
    m, d, f = BATCH * SEQ, cfg.d_model, cfg.d_ff

    def enc(*shape):
        return encode(rng.normal(size=shape).astype(np.float32) * 0.05,
                      LNS16)

    x, w, dy = enc(m, d), enc(d, f), enc(m, f)
    kernels = {"fwd": (lns_matmul_kernel, (x, w), (x, w)),
               "dx": (lns_matmul_dx_kernel, (dy, w), (dy, w.T)),
               "dw": (lns_matmul_dw_kernel, (x, dy), (x.T, dy))}

    for delta in KERNEL_DELTAS:
        spec = DELTA_NAMES[delta]
        eng = DeltaEngine(spec, LNS16)
        emulate = jax.jit(lambda a, b: lns_matmul(a, b, eng,
                                                  order="sequential"))
        for name, (kernel, args, (a, b)) in kernels.items():
            c0 = clock.seconds
            got = jax.block_until_ready(kernel(*args, fmt=LNS16, spec=spec))
            compile_s = clock.seconds - c0
            t0 = time.perf_counter()
            jax.block_until_ready(kernel(*args, fmt=LNS16, spec=spec))
            kernel_s = time.perf_counter() - t0
            # The emulation materializes every (row, k, n) product and
            # walks k one step at a time, too slow for every row: it checks
            # 16 output rows spread over all the kernel's row tiles.
            rows = slice(None, None, a.shape[0] // 16)
            want = emulate(a[rows], b)
            equal = bool(np.array_equal(np.asarray(got.code)[rows],
                                        np.asarray(want.code))
                         and np.array_equal(np.asarray(got.sign)[rows],
                                            np.asarray(want.sign)))
            say("kernels-qwen3", delta=delta, op=name,
                shape=f"{m}x{d}x{f}", pallas_eq_emulate=equal,
                compile_s=round(compile_s, 3), kernel_s=round(kernel_s, 4))
            check(equal, f"{delta} {name} kernel differs from the emulated "
                  "MAC")


#: The kernels-gmm phase: rows of each of 16 held experts (uneven, one
#: empty, four over a 128-row tile; 1,536 routed of a 6,144-row bound),
#: and the widths.
GMM_SIZES = (96, 0, 200, 13, 130, 96, 1, 232, 96, 96, 150, 40, 96, 96, 100,
             94)
GMM_ROWS, GMM_D, GMM_DE = 6144, 2048, 1408


def phase_kernels_gmm(clock):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import LNS16, DELTA_DEFAULT
    from repro.kernels.lns_matmul.grouped import (lns_gmm_dw_pallas,
                                                  lns_gmm_dx_pallas,
                                                  lns_gmm_pallas)
    from repro.kernels.lns_matmul.lns_matmul import (lns_matmul_dw_pallas,
                                                     lns_matmul_dx_pallas,
                                                     lns_matmul_pallas)
    rng = np.random.default_rng(SEED)
    kw = dict(fmt=LNS16, spec=DELTA_DEFAULT)

    def enc(*shape):
        from repro.core import encode
        a = encode(rng.normal(size=shape).astype(np.float32) * 0.05, LNS16)
        return a.code, a.sign.astype(jnp.int32)

    g, m, d, de = len(GMM_SIZES), GMM_ROWS, GMM_D, GMM_DE
    sizes = jnp.asarray(GMM_SIZES, jnp.int32)
    x, w, dy = enc(m, d), enc(g, d, de), enc(m, de)
    grouped = {
        "fwd": (jax.jit(lambda a, b, c, e, s: lns_gmm_pallas(a, b, c, e, s,
                                                              **kw)),
                x + w, lambda r, e: lns_matmul_pallas(
                    x[0][r], x[1][r], w[0][e], w[1][e], **kw)),
        "dx": (jax.jit(lambda a, b, c, e, s: lns_gmm_dx_pallas(a, b, c, e, s,
                                                                **kw)),
               dy + w, lambda r, e: lns_matmul_dx_pallas(
                   dy[0][r], dy[1][r], w[0][e], w[1][e], **kw)),
        "dw": (jax.jit(lambda a, b, c, e, s: lns_gmm_dw_pallas(a, b, c, e, s,
                                                                **kw)),
               x + dy, lambda r, e: lns_matmul_dw_pallas(
                   x[0][r], x[1][r], dy[0][r], dy[1][r], **kw)),
    }
    ends = np.cumsum(GMM_SIZES)
    for name, (fn, args, plain) in grouped.items():
        c0 = clock.seconds
        got = jax.block_until_ready(fn(*args, sizes))
        compile_s = clock.seconds - c0
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, sizes))
        kernel_s = time.perf_counter() - t0
        equal = True
        for e, (lo, hi) in enumerate(zip(ends - GMM_SIZES, ends)):
            if hi == lo:
                if name == "dw":
                    equal &= bool(np.all(np.asarray(got[0][e])
                                         == LNS16.zero_code))
                continue
            want = plain(slice(int(lo), int(hi)), e)
            have = ((got[0][e], got[1][e]) if name == "dw" else
                    (got[0][lo:hi], got[1][lo:hi]))
            equal &= all(np.array_equal(np.asarray(h), np.asarray(v))
                         for h, v in zip(have, want))
        if name != "dw":
            equal &= bool(np.all(np.asarray(got[0][ends[-1]:])
                                 == LNS16.zero_code))
        say("kernels-gmm", op=name, groups=g, rows=f"{ends[-1]}/{m}",
            shape=f"{d}x{de}" if name != "dx" else f"{de}x{d}",
            grouped_eq_plain=equal, compile_s=round(compile_s, 3),
            kernel_s=round(kernel_s, 4))
        check(equal, f"grouped {name} differs from per-expert plain "
              "launches")


def phase_qwen3(clock):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.core.plan import NumericsPlan
    from repro.nn import init_params, loss_fn
    from repro.nn.model import known_layer_paths
    from repro.optim.optimizers import SGDConfig
    from repro.train import init_train_state, make_train_step

    cfg = get_config("qwen3-1.7b").with_(n_layers=LAYERS,
                                          numerics=QWEN3_PLAN,
                                          remat="none")
    plan = NumericsPlan.parse(QWEN3_PLAN)
    lanes = {p: plan.runtime_for(p).lane for p in known_layer_paths(cfg)}
    say("qwen3", plan=str(plan), layers=cfg.n_layers, d_model=cfg.d_model,
        d_ff=cfg.d_ff, heads=f"{cfg.n_heads}/{cfg.n_kv_heads}",
        d_head=cfg.d_head, vocab=cfg.vocab_size, lanes=lanes)
    check(all(lane == "pallas-hw" for p, lane in lanes.items()
              if plan.resolve(p).delta_spec is not None),
          f"⊞-MAC layers not on the compiled kernels: {lanes}")

    rng = np.random.default_rng(SEED)
    toks = rng.integers(0, cfg.vocab_size, size=(BATCH, SEQ + 1))
    batch = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
             "labels": jnp.asarray(toks[:, 1:], jnp.int32)}
    params = init_params(jax.random.PRNGKey(SEED), cfg)
    ref_loss = float(jax.jit(lambda p, b: loss_fn(
        p, b, cfg.with_(numerics="fp32")))(params, batch))
    opt = SGDConfig(lr=1e-3)
    state = init_train_state(params, opt)
    step = jax.jit(make_train_step(cfg, opt), donate_argnums=0)
    c0, t0 = clock.seconds, time.perf_counter()
    compiled = step.lower(state, batch).compile()
    say("qwen3", tokens=f"{BATCH}x{SEQ}",
        compile_s=round(time.perf_counter() - t0, 3),
        jax_compile_s=round(clock.seconds - c0, 3))
    losses = []
    for i in range(2):
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        loss = float(metrics["loss"])   # waits for the device
        losses.append(loss)
        say("qwen3", step=i + 1, loss=loss,
            step_s=round(time.perf_counter() - t0, 3))
    check(all(math.isfinite(v) for v in losses), f"nonfinite loss {losses}")
    say("qwen3", fp32_reference_loss=ref_loss)
    # At initialization the logits are near zero and both losses sit near
    # ln(vocab); the ⊞-MAC arithmetic perturbs them by a few percent.
    check(abs(losses[0] - ref_loss) <= 0.05 * ref_loss,
          f"first loss {losses[0]} far from the float32 model's {ref_loss}")


def phase_dp4(clock):
    from repro.distributed.lns_dp import run_device_count_invariance_check
    c0, t0 = clock.seconds, time.perf_counter()
    ok, runs = run_device_count_invariance_check(
        (4,), steps=STEPS, batch=64,
        numerics="lns16-train-pallas,reduce.grad_segments=4",
        n_in=784, n_hidden=100, n_out=10, seed=SEED)
    wall = time.perf_counter() - t0
    say("dp4", devices=4, grad_segments=4, batch=64, steps=STEPS,
        loss=runs[4]["loss"],
        weight_codes_eq_one_device_reference=runs[4]["matches_reference"],
        compile_s=round(clock.seconds - c0, 3),
        other_s=round(wall - clock.seconds + c0, 3))
    check(ok, "4-device ⊞-allreduce weight codes differ from the "
          "one-device reference")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {device['count']} "
              f"{device['platform']} device(s) ({device['kind']})",
              file=sys.stderr)
        return 2
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices; JAX found {device['count']}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    say("device", **device, compile_cache=enable_compile_cache())
    clock = CompileClock()
    phases = ([phase_dp4] if args.chips == 4
              else [phase_mlp, phase_kernels_qwen3, phase_kernels_gmm,
                    phase_qwen3])
    failed = []
    for phase in phases:
        name = phase.__name__[len("phase_"):]
        t0, cache0 = time.perf_counter(), dict(clock.cache)
        try:
            phase(clock)
        except Exception as e:
            traceback.print_exc()
            say(name, status="FAILED", error=repr(e))
            failed.append(name)
            continue
        say(name, status="ok", wall_s=round(time.perf_counter() - t0, 3),
            compile_cache={k: v - cache0[k] for k, v in clock.cache.items()})
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
