#!/usr/bin/env python3
"""The benchmark: one cell, one seed, one run, in this one process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is ``bench/workloads/<cell>.json``: a configuration
(``bench/configs/<config>.json`` and ``.py``), a traffic mix
(``bench/traffic/<traffic>.json``) and the chips it needs.  The run
refuses anything but a TPU, turns on the compile cache at a fixed path in
the checkout, makes the weights on the device and the inputs on the host
from ``--seed``, and drives the program's own train step through its first
steps: that compiles every shape the window uses, and what those steps
produce is what the correctness check compares with the plain reference.
Set-up ends there.  Then the same step runs for ``--seconds``: with
``--trace 0`` that window gives the cell's end-to-end metrics, with
``--trace 1`` a profiler trace of it gives the per-layer metrics
(``bench/metrics/<name>.py``).  After the window the program's state is
freed and the reference runs.

The last line of standard output is one JSON object; the numbers compared
and their limits come last there and on standard error.  Every ⊞-MAC layer
must run on the compiled kernels (``pallas-hw``), or the run fails.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import compare  # noqa: E402
import traffic as traffic_mod  # noqa: E402
from common import (ROOT, CompileClock, enable_compile_cache,  # noqa: E402
                    load_json, load_module, span)


class Ctx:
    """What a per-layer metric's reader gets: the reduced trace, the steps
    it holds, the cell's counts, the chips and their peaks."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def metrics_of(cell_name: str, bench: dict) -> tuple:
    """The end-to-end and per-layer metrics ``BENCHMARK.json`` gives this
    cell: a metric with ``workloads`` where it lists the cell, one
    without it wherever the cell reports what it moves."""
    e2e = [m for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if cell_name in m["workloads"]] + \
        [m for m in bench["per_layer"]
         if "workloads" not in m and m["moves"] in names]
    return e2e, layer


def _options():
    """Host spans, and no tracing of every Python call (which would slow
    the host loop the trace is meant to show)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _drive(system, gen, start, seconds, max_steps, read_each):
    """Run the step until ``seconds`` have passed (or ``max_steps``), then
    wait for the device.  Returns (steps, elapsed, losses)."""
    losses, i = [], start
    t0 = time.perf_counter()
    while True:
        with span("bench.feed"):
            batch = gen.batch(i)
        with span("bench.step"):
            loss = system.step(batch)
        if read_each:
            with span("bench.loss_read"):
                loss = float(loss)
        losses.append(loss)
        i += 1
        if (time.perf_counter() - t0 >= seconds
                or (max_steps and i - start >= max_steps)):
            break
    with span("bench.sync"):
        system.block()
    return i - start, time.perf_counter() - t0, losses


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t0: float | None = None, phases: dict | None = None,
             variant: str | None = None, require_compiled: bool = True,
             config_overrides=None) -> dict:
    """Run one cell and return the result line as a dict.

    ``variant`` and ``config_overrides`` exist for the benchmark's own
    tests and readings: a control or a planted fault in place of the
    program, and a configuration small enough for a CPU."""
    import jax

    t0 = T0 if t0 is None else t0
    phases = dict(phases or {})
    cell = load_json("workloads", cell_name + ".json")
    bench = load_json("..", "BENCHMARK.json")
    e2e, layer = metrics_of(cell_name, bench)
    devices = jax.devices()
    phases["device_init_s"] = phases.get("device_init_s", 0.0) + (
        time.perf_counter() - t0 - sum(phases.values()))
    c = load_json("configs", cell["config"] + ".json")
    c.update(config_overrides or {})
    mod = load_module("configs", cell["config"])
    gen = traffic_mod.make(cell["traffic"], c, seed)
    clock = CompileClock()
    if variant == "no_exchange":
        system = mod.RefSystem(c, cell, seed, variant)
    else:
        system = mod.System(c, cell, seed, variant)
    off = {p: lane for p, lane in system.lanes().items()
           if lane != "pallas-hw"}
    if require_compiled and (off or variant == "no_exchange"):
        raise RuntimeError(f"⊞-MAC layers off the compiled kernels: {off}")
    phases["weights_and_data_s"] = (time.perf_counter() - t0
                                    - sum(phases.values()))

    # The first steps: they compile (or load) every program the window
    # runs, and the check compares what they produce.
    n_check = cell["check_steps"]
    prog = {"losses": []}
    for i in range(n_check):
        with span("bench.feed"):
            batch = gen.batch(i)
        with span("bench.step"):
            loss = system.step(batch)
        prog["losses"].append(float(loss))
        if i == 0:
            prog["grad"] = system.observe("grad")
            phases["first_step_s"] = (time.perf_counter() - t0
                                      - sum(phases.values()))
    prog["change"] = system.observe("change")
    phases["compile_s"] = clock.seconds
    phases["cache"] = dict(clock.cache)
    setup_s = time.perf_counter() - t0
    compiles_before = clock.events

    # The window.
    counts = mod.counts(c, gen, cell["chips"])
    trace_dir = os.path.join(ROOT, ".bench_trace", str(os.getpid()))
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir, profiler_options=_options())
    steps, elapsed, losses = _drive(
        system, gen, n_check, seconds, cell["trace_steps"] if trace else 0,
        cell["read_loss_each_step"])
    if trace:
        jax.profiler.stop_trace()
    compiled_in_window = clock.events - compiles_before
    losses = [float(v) for v in jax.device_get(losses)]
    failed = sum(1 for v in losses if not math.isfinite(v))
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell["chips"]])
    system.free()
    del system
    gc.collect()

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    out = {"attempted": steps, "failed": failed, "device": device}
    if trace:
        import trace as tr
        import roofline
        t = tr.Trace.from_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = Ctx(trace=t, steps=steps, counts=counts, chips=cell["chips"],
                  peaks=roofline.peaks(devices[0].device_kind)
                  if devices[0].platform == "tpu" else None)
        out["metrics"] = {}
        for m in layer:
            v = load_module("metrics", m["name"]).read(ctx)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = t.busy_s()
        device["window_s"] = t.window_s
        out["breakdown"] = t.breakdown()
    else:
        rate = counts["items_per_step"] * steps / elapsed
        vals = {m["name"]: (setup_s if m["name"] == "setup_s" else rate)
                for m in e2e}
        out["metrics"] = {m["name"]: {"value": vals[m["name"]],
                                      "unit": m["unit"]} for m in e2e}

    # The check, once the program's state is gone.
    t_check = time.perf_counter()
    ref = mod.reference(c, cell, seed, [gen.batch(i)
                                        for i in range(n_check)])
    nums = {**compare.numbers(prog, ref), "failed_steps": failed,
            "compiles_in_window": compiled_in_window}
    checks = compare.check(nums, {**cell["limits"], "failed_steps": 0,
                                  "compiles_in_window": 0})
    out["correct"] = all(v["ok"] for v in checks.values())
    out["setup"] = {**phases, "setup_s": setup_s, "window_s": elapsed,
                    "steps": steps,
                    "check_s": time.perf_counter() - t_check}
    out["sides"] = {side: {"losses": d["losses"],
                           **{w: d[w]["norms"] for w in ("grad", "change")}}
                    for side, d in (("program", prog), ("reference", ref))}
    out["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                     for k, v in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_json("workloads", args.workload + ".json")
    import jax
    phases = {"imports_s": time.perf_counter() - T0}
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s) "
              f"({devs[0].device_kind})", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    enable_compile_cache()
    res = run_cell(args.workload, args.seed, args.seconds,
                   bool(args.trace), phases=phases)
    for k, v in res["checks"].items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps({"correct": res["correct"],
                      "attempted": res["attempted"],
                      "failed": res["failed"], "metrics": res["metrics"],
                      "device": res["device"],
                      **({"breakdown": res["breakdown"]}
                         if "breakdown" in res else {}),
                      "setup": res["setup"], "checks": res["checks"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
