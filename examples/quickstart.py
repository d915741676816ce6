"""Quickstart: the LNS number system, the paper's MLP, and the kernel.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import (DELTA_DEFAULT, LNS16, DeltaEngine, NumericsSpec,
                        boxdot, boxplus, decode, encode, lns_matmul)
from repro.kernels import lns_matmul_kernel, lns_matmul_trainable
from repro.paper import run_experiment

print("=== 1. LNS arithmetic (paper Sec. 2-3) ===")
fmt = LNS16
eng = DeltaEngine(DELTA_DEFAULT, fmt)      # 20-entry LUT, d_max=10, r=1/2
x = encode(np.float32(3.25), fmt)
y = encode(np.float32(-1.5), fmt)
print(f"3.25    → code={int(x.code)} sign={int(x.sign)}")
print(f"3.25 ⊡ -1.5 = {float(decode(boxdot(x, y, fmt), fmt)):.4f}  (exact: -4.875)")
print(f"3.25 ⊞ -1.5 = {float(decode(boxplus(x, y, eng), fmt)):.4f}  (exact: 1.75)")

print("\n=== 2. Multiplication-free matmul (eq. 10) ===")
rng = np.random.default_rng(0)
A = rng.normal(size=(4, 64)).astype(np.float32)
B = rng.normal(size=(64, 3)).astype(np.float32)
Z = decode(lns_matmul(encode(A, fmt), encode(B, fmt), eng), fmt)
rel = np.median(np.abs(Z - A @ B) / np.abs(A @ B))
print(f"emulated ⊞-MAC matmul median rel err vs float: {rel:.3f}")

Zk = decode(lns_matmul_kernel(encode(A, fmt), encode(B, fmt), fmt=fmt,
                              spec=DELTA_DEFAULT, block_m=8, block_n=8,
                              block_k=16), fmt)
print(f"Pallas kernel matches emulation structurally; "
      f"median rel err: {np.median(np.abs(Zk - A @ B) / np.abs(A @ B)):.3f}")

print("\n=== 3. One spec, every numerics axis (NumericsSpec → LNSRuntime) ===")
# Every axis of the arithmetic — format, Δ approximation, which tensors
# are quantized, ⊞-MAC execution backend, interpret mode, DP gradient
# reduction — lives in ONE frozen, serializable descriptor.  Parse an
# alias, or an alias plus key=value overrides; str() round-trips to the
# canonical form (so specs travel through CLIs and checkpoint metadata):
spec = NumericsSpec.parse("lns16-train-pallas")
print(f"spec: {spec}")
print(f"  fmt={spec.fmt.name} delta={spec.delta_spec.kind} "
      f"quantize={spec.quantize} backend={spec.backend} "
      f"reduce.mode={spec.reduce.mode}")
# Typed overrides replace policy-name surgery; invalid values raise with
# the valid list:
print(f"  with_(backend='emulate') → {spec.with_(backend='emulate')}")
print(f"  parse('lns16-train-emulate,backend=pallas') → "
      f"{NumericsSpec.parse('lns16-train-emulate,backend=pallas')}")

# The spec resolved once is an LNSRuntime: it owns the cached matmul
# backend (emulate = pure-jnp sequential MAC, pallas = the blocked TPU
# kernels, interpret mode on CPU — bit-exact to each other):
for be_name in ("emulate", "pallas"):
    rt = spec.with_(backend=be_name).runtime(block_m=8, block_n=8,
                                             block_k=16)
    dy = encode(np.ones((4, 3), np.float32), fmt)
    dx = rt.matmul.matmul_dx(dy, encode(B, fmt))  # dY ⊞ Bᵀ, no transpose
    print(f"backward dX on {be_name:7s}: first code = {int(dx.code[0, 0])}")

# jax.grad flows through the same path via the custom_vjp boundary — the
# kernels take the backend the runtime resolved:
import jax
rt = NumericsSpec.parse("lns16-train-pallas,delta=lut640").runtime(
    block_m=8, block_n=8, block_k=16)
g = jax.grad(lambda a: lns_matmul_trainable(a, B, rt.matmul).sum())(A)
print(f"jax.grad through the Pallas ⊞-MAC: gA.shape = {g.shape}")

print("\n=== 4. End-to-end log-domain training (paper Sec. 4-5) ===")
# The paper MLP takes the same descriptor (numerics= / MLPConfig.spec=);
# emulate and pallas produce bit-identical weight trajectories.
r = run_experiment("lns", "mnist", numerics="lns16-train-emulate",
                   epochs=1, max_steps_per_epoch=80)
print(f"LNS-16 LUT MLP, 80 steps: val acc {r.val_curve[-1]:.3f}")
r = run_experiment("float", "mnist", epochs=1, max_steps_per_epoch=80)
print(f"float32 MLP,   80 steps: val acc {r.val_curve[-1]:.3f}")
print("(run benchmarks/run.py for the full Table-1 grid)")

# The data-parallel switch rides the same spec: reduce.* selects the
# gradient-reduce semantics, so any device count dividing
# reduce.grad_segments yields bit-identical weight codes:
#   run_experiment("lns", "mnist", batch_size=8, data_parallel=2,
#                  numerics="lns16-train-pallas,reduce.grad_segments=4")
# (reduce.mode=float-psum is the fast non-bit-exact escape hatch; on
# CPU emulate extra devices with
#  XLA_FLAGS=--xla_force_host_platform_device_count=8 — see
#  examples/train_data_parallel.py for the full 1/2/4-device drill.)
from repro.distributed.lns_dp import run_device_count_invariance_check
ok, _ = run_device_count_invariance_check(
    (1,), steps=2, batch=8,
    numerics="lns16-train-pallas,reduce.grad_segments=4")
print(f"DP ⊞-allreduce schedule == single-device sequential baseline: {ok}")

print("\n=== 5. Per-layer mixed-format plans (NumericsPlan) ===")
# Arithmetic is a per-layer property: a NumericsPlan maps layer-path glob
# patterns to spec overrides on top of a default spec.  Here the hidden
# layer (the bulk of the MACs: 784×100 vs 100×10 weights) drops to lns12
# — a 25% narrower datapath — while the softmax-critical output layer
# keeps lns16.  parse/str round-trip losslessly, same as specs:
from repro.core import NumericsPlan
plan = NumericsPlan.parse("lns16-train-emulate;hidden=fmt:lns12")
print(f"plan: {plan}")
print(f"  hidden resolves to fmt={plan.resolve('hidden').fmt.name}, "
      f"out to fmt={plan.resolve('out').fmt.name}")
# Mixed-format training end-to-end, vs the uniform-lns16 run from §4
# (exact integer barrel-shift conversions at the layer boundary; the
# emulate and pallas backends stay bit-identical under mixed plans too):
r16 = run_experiment("lns", "mnist", numerics="lns16-train-emulate",
                     epochs=1, max_steps_per_epoch=80)
r12 = run_experiment("lns", "mnist", numerics=plan,
                     epochs=1, max_steps_per_epoch=80)
print(f"uniform lns16          : val acc {r16.val_curve[-1]:.3f}")
print(f"lns12 hidden / lns16 out: val acc {r12.val_curve[-1]:.3f} "
      f"(Δ {r12.val_curve[-1] - r16.val_curve[-1]:+.3f} — the 12-bit "
      f"hidden layer costs little; the paper's accuracy cliff lives in "
      f"the softmax/output path, which stays 16-bit)")

print("\n=== 6. Fused epilogues + autotuned blocks (one pass per matmul) ===")
# The train step's epilogues — bias ⊞, llrelu, format-boundary
# requantize, and the ⊞-SGD (momentum + weight-decay) update — run at
# the kernels' accumulator flush instead of as separate passes over
# every tensor (MLPConfig.fused, on by default and bit-identical to the
# unfused composition).  Block sizes are a spec axis: blocks=auto defers
# to the per-(spec, op, shape) autotuner (kernels/autotune.py), whose
# measured choices persist under .lns_autotune/.  Explicit per-layer
# tiles work too: "lns16-train-pallas;hidden=blocks:256x128x128".
import time

from repro.core import DELTA_DEFAULT as _LUT20
from repro.kernels import autotune
from repro.paper.mlp import MLPConfig, make_mlp

xb = rng.uniform(0, 1, size=(64, 784)).astype(np.float32)
yb = rng.integers(0, 10, size=(64,)).astype(np.int32)

# Prime the autotuner eagerly (measured search, cached on disk under
# .lns_autotune/ — re-runs are free) for the two layer shapes of the
# paper MLP; inside jit it would fall back to the deterministic
# heuristic instead of timing.
picks = autotune.prime_matmul(64, 784, 100, fmt=LNS16, spec=_LUT20)
autotune.prime_matmul(64, 100, 10, fmt=LNS16, spec=_LUT20)
print(f"autotuned hidden-layer blocks: {picks}")


# Interleaved best-of-reps: the two variants are timed back-to-back per
# rep so machine-speed drift hits both equally (same discipline as
# benchmarks/kernel_bench.py).
_steps = {}
for _name, _cfg in (
        ("unfused", MLPConfig(spec="lns16-train-pallas", fused=False)),
        ("fused", MLPConfig(spec="lns16-train-pallas,blocks=auto",
                            fused=True))):
    _model = make_mlp("lns", _cfg)
    _p = _model.init(jax.random.PRNGKey(0))
    _fn = (lambda mo, pp: lambda: np.asarray(
        mo.train_step(pp, xb, yb)[0]["w1"].code))(_model, _p)
    _fn()                                        # compile + warm
    _steps[_name] = [_fn, float("inf")]
for _ in range(3):
    for _slot in _steps.values():
        _t0 = time.perf_counter()
        _slot[0]()
        _slot[1] = min(_slot[1], time.perf_counter() - _t0)
before, after = _steps["unfused"][1] * 1e3, _steps["fused"][1] * 1e3
print(f"unfused step, fixed 32³ blocks : {before:6.0f} ms")
print(f"fused step,   blocks=auto      : {after:6.0f} ms "
      f"({before / after:.2f}x — bit-identical weight codes; with "
      f"momentum>0 the ⊞-momentum update fuses into the dW flush too)")
print("(interpret-mode timings late in a busy process understate the "
      "win; benchmarks/kernel_bench.py measures the same rows in a "
      "fresh process — see the train_step rows in BENCH_kernels.json)")

print("\n=== 7. Serving: chunked prefill + paged KV cache + batching ===")
# The serving engine turns max_len into a *token budget* over fixed-size
# KV blocks: each layer holds a pool of num_blocks physical blocks of
# block_size positions, a per-slot block table maps logical -> physical,
# and block 0 is the reserved null write sink.  Budget math:
#   blocks/request = ceil(min(max_len, prompt + max_new) / block_size)
# reserved in full at admission, so an admitted request never OOMs
# mid-flight.  Prompts are spliced in prefill_chunk-token chunks by a
# dedicated jitted graph — at most one chunk per engine step, so a long
# prompt never stalls concurrent decodes.  Greedy outputs are
# bit-identical to the dense token-by-token reference (pinned in
# tests/test_serve_engine.py).
from repro.nn import init_params
from repro.nn.config import ModelConfig
from repro.serve import ServeConfig, ServingEngine, TERMINAL

_scfg = ModelConfig(name="qs-serve", family="dense", n_layers=2,
                    d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                    vocab_size=64, d_head=16, vocab_pad_to=64,
                    numerics="fp32", param_dtype="float32", remat="none",
                    q_chunk=8)
_sp = init_params(jax.random.PRNGKey(0), _scfg)
_sc = ServeConfig(max_batch=2, max_len=24, block_size=4, prefill_chunk=4)
engine = ServingEngine(_scfg, _sp, _sc)
print(f"pool: {engine.bm.capacity} blocks x {_sc.block_size} lines "
      f"= {engine.bm.capacity * _sc.block_size}-token budget "
      f"({_sc.max_batch} slots x max_len {_sc.max_len})")

# Async surface: submit() -> rid immediately; step() advances admission,
# one prefill chunk, and one batched decode; poll(rid) reads state.
_rng = np.random.default_rng(0)
rids = [engine.submit(_rng.integers(3, 64, size=n), max_new=4,
                      deadline_steps=50) for n in (5, 7, 3)]
while any(engine.poll(r).state not in TERMINAL for r in rids):
    engine.step()
for r in rids:
    req = engine.poll(r)
    blocks = engine.bm.blocks_for(min(_sc.max_len,
                                      req.prompt_len + req.max_new))
    print(f"  rid {r}: {req.state} prompt={req.prompt_len} "
          f"reserved {blocks} blocks -> {list(req.output)}")
engine.bm.check_conserved()   # free-list conservation: no leaks
print(f"occupancy {engine.occupancy:.2f}/{_sc.max_batch} slots, "
      f"{engine.stats['prefill_chunks']} prefill chunks, "
      f"{engine.bm.available}/{engine.bm.capacity} blocks free again")
# Decode/prefill matmuls run the runtime's *inference* dispatch: on
# kernel-path specs that is matmul_fused (the fused forward-epilogue
# surface from §6) — bit-identical to the training forward by the
# fusion contract, one launch per matmul instead of kernel + epilogues.
print(f"numerics (fused-infer dispatch): {engine.matmul_path}")

print("\n=== 8. Watching your numerics: the obs telemetry subsystem ===")
# Telemetry is observer-only by contract: counters are pure reads of op
# inputs/outputs, collected as extra int32 outputs of the model's second
# step entry point, train_step_metrics (which never donates, and arms a
# FaultPlan keyed by step= — §10).  The plain train_step never pushes a
# collector, so its graph is byte-for-byte the uninstrumented one, and
# metrics-on weight codes are bit-identical to metrics-off (pinned in
# tests/test_obs.py).  Per-layer opt-in via the plan's `metrics` axis:
# off | counters | full (full adds the Δ-LUT |d|-occupancy histogram).
from repro.obs import DHIST_EDGES, MetricsRegistry

_ocfg = MLPConfig(n_in=24, n_hidden=16, n_out=10, lr=0.01,
                  spec="lns16-train-emulate;hidden=fmt:lns12,metrics:full",
                  matmul_block=8)
_om = make_mlp("lns", _ocfg)
_op = _om.init(jax.random.PRNGKey(0))
_ox = np.random.default_rng(0).normal(size=(8, 24)).astype(np.float32)
_oy = np.random.default_rng(1).integers(0, 10, size=(8,))
(_op2, _loss), _taps = _om.train_step_metrics(_op, _ox, _oy)
(_op2_plain, _loss_plain) = _om.train_step(_op, _ox, _oy)
assert np.array_equal(_op2["w1"].code, _op2_plain["w1"].code)
print(f"metrics-on == metrics-off weight codes: True "
      f"({len(_taps)} tap labels collected)")

# Structured sinks: a MetricsRegistry aggregates taps (with the resolved
# execution lane per layer) into labeled counter/histogram rows; JsonlSink
# flushes them per step.  The CLI surfaces:
#   python -m repro.launch.train --arch ... --metrics out.jsonl
#   python benchmarks/serve_bench.py --micro --metrics serve.jsonl
#   python benchmarks/metrics_report.py out.jsonl   # per-layer summary
_reg = MetricsRegistry(base_labels={"spec": str(_om.plan)})
_reg.merge_numerics_taps(jax.device_get(_taps), lanes=_om.lanes())
_sat = _reg.counter_value("numerics.sat", layer="hidden", op="act",
                          lane="emulate")
_el = _reg.counter_value("numerics.elems", layer="hidden", op="act",
                         lane="emulate")
print(f"hidden/act saturation: {_sat}/{_el} codes at lns12 code_max")
_dh = [r for r in _reg.rows() if r["kind"] == "bucketed_histogram"
       and r["layer"] == "hidden"][0]
print(f"Δ-LUT occupancy (edges {DHIST_EDGES}): {_dh['counts']} — last "
      f"bucket is |d| beyond the paper LUT's d_max (Δ≈0 region)")

print("\n=== 9. Plan autosearch: derive the mixed plan automatically ===")
# §5 hand-wrote the lns12-hidden plan.  The search subsystem derives it:
# sweep per-layer fmt rules over NumericsPlan candidates, score each by
# short-horizon accuracy vs the anchor + a deterministic datapath cost,
# rank the narrowing order by the §8 obs counters, and keep the Pareto
# frontier.  Seeded and journaled — run twice, byte-identical frontier;
# kill it mid-sweep and rerun, it resumes from the journal.
#   CLI: python -m repro.launch.search --smoke   (what CI runs)
from repro.search import PlanSearch, SearchConfig, SearchSpace
from repro.search.report import frontier_table

_sspace = SearchSpace.for_paper_mlp("lns16-train-emulate",
                                    fmts=("lns16", "lns12"))
_scfg = SearchConfig(epochs=1, steps_per_epoch=6, batch_size=5, seed=0,
                     refine_generations=1, refine_population=2)
_search = PlanSearch(_sspace, _scfg)
_sres = _search.run()
print(f"evaluated {len(_sres.evals)} candidate plans "
      f"(narrowing order from obs counters: {', '.join(_sres.order)})")
print(frontier_table(_sres.frontier, _sres.winner))
print(f"winning plan — paste into launch/train.py:")
print(f"  --numerics '{_sres.winner['plan']}'")

print("\n=== 10. Fault drill: inject → detect → recover ===")
# Faults are injected, never accidental: a seed-keyed FaultPlan (same
# glob-rule grammar as NumericsPlan) flips weight/activation code bits,
# pins lanes at saturation, corrupts Δ-LUT entries, or drops DP segment
# partials — identically on both lanes, and as a true no-op (identical
# traced graph) when no plan is active.  Guardrails watch the §8 metrics
# taps and recover: snapshot rollback, per-layer format widening (a plan
# override + exact code conversion), DP recompute-and-splice.
#   CLI: python -m repro.launch.drill --smoke        (the CI chaos job)
#        python benchmarks/fault_drill_bench.py --selfcheck
from repro.paper.mlp import MLPConfig, make_mlp
from repro.resil import GuardConfig, GuardedTrainer

_fcfg = MLPConfig(n_in=12, n_hidden=9, n_out=4, lr=0.01, momentum=0.9,
                  spec="lns16-train-emulate;hidden=fmt:lns12,metrics:full",
                  matmul_block=8,
                  faults="seed=7,start=3;hidden=sat_lanes:4")
_fm = make_mlp("lns", _fcfg)
_fp = _fm.init(jax.random.PRNGKey(0))
_ft = GuardedTrainer(_fm, _fp, _fm.init_momentum(_fp),
                     guard=GuardConfig(sat_frac=0.10))
_frng = np.random.default_rng(5)
for _ in range(5):
    _fr = _ft.step(_frng.normal(size=(8, 12)).astype(np.float32),
                   _frng.integers(0, 4, size=(8,)))
    if _fr["action"]:
        print(f"step {_fr['step']}: "
              f"{[a.kind for a in _fr['alerts']]} → {_fr['action']}")
print(f"recovery events: {[e['action'] for e in _ft.events]} — hidden "
      f"widened from lns12 to lns16 under a stuck-at-saturation storm")
