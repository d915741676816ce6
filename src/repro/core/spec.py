"""Unified numerics descriptor: ``NumericsSpec`` → ``LNSRuntime``.

The paper's scheme is *one* arithmetic with several orthogonal axes —
format (lns16/lns12), Δ-approximation spec, which tensors are quantized,
matmul execution backend, interpret mode, kernel block sizes (fixed,
explicit, or autotuned per op+shape), and the data-parallel gradient
reduction semantics.  Historically each axis grew its own stringly-typed
policy name (``lns16-train-pallas``, …) and its own loose config knob
(``matmul_backend=``, ``reduce_mode=``, ``grad_segments=``) threaded
through ``MLPConfig`` / ``TrainConfig`` / ``DPConfig`` separately.  This
module collapses all of that into two objects:

* :class:`NumericsSpec` — a frozen, hashable, *serializable* description
  of the arithmetic.  ``NumericsSpec.parse`` accepts a registry alias
  (``"lns16-train-pallas"``), a ``key=value`` list, or an alias plus
  overrides (``"lns16-train-pallas,reduce.mode=float-psum"``); ``str``
  round-trips losslessly to the canonical form (registry alias when one
  matches exactly, else nearest alias + sorted overrides), so specs are
  CLI- and checkpoint-metadata-friendly.

* :class:`LNSRuntime` — the spec *resolved once*: owns the cached
  :class:`~repro.core.lns.LNSMatmulBackend`, the per-op numerics-policy
  behavior every ``repro.nn`` layer routes matmuls through (``q_param`` /
  ``q_act`` / ``linear``), the shared Δ engine, and the data-parallel
  reduce plan (:meth:`LNSRuntime.dp_config`).

Adding a new numerics axis is now a one-dataclass-field change here, not
an N-file threading exercise: every consumer reads the same object.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .delta import (DELTA_BITSHIFT, DELTA_DEFAULT, DELTA_EXACT, DELTA_SOFTMAX,
                    DeltaSpec)
from .formats import FORMATS, LNS12, LNS16, LNSFormat
from .lns import (MATMUL_BACKENDS, LNSMatmulBackend, _cached_engine,
                  parse_blocks, resolve_blocks_arg)

#: Valid values of every enum-ish axis (single source of truth; the
#: distributed package imports REDUCE_MODES from here).
REDUCE_MODES = ("boxplus", "float-psum")
REDUCE_SCHEDULES = ("sequential", "tree")
INTERPRET_MODES = ("auto", "on", "off")
#: The ``metrics`` axis: telemetry *eligibility* per spec (plan-addressable
#: per layer).  "counters" — saturation/flush counters when a collector is
#: active; "full" — additionally the Δ-LUT |d| occupancy histogram (runs a
#: shadow sequential ⊞-MAC: observably slower, results unchanged); "off" —
#: this layer never reports.  The master switch is *which entry point* you
#: call (``train_step`` vs ``train_step_metrics``): with no collector
#: active, every mode is a true no-op and the jitted graphs are identical.
METRICS_MODES = ("off", "counters", "full")
QUANTIZE_AXES = ("params", "acts", "grads")
COMPUTE_DTYPES = ("float32", "bfloat16", "float16")
#: Named Δ specs (the serializable vocabulary; arbitrary LUTs round-trip
#: through the generic ``lut:<d_max>:<r>`` form).
DELTA_NAMES = {
    "lut20": DELTA_DEFAULT,        # paper default: d_max=10, r=1/2
    "lut640": DELTA_SOFTMAX,       # softmax-grade: d_max=10, r=1/64
    "bitshift": DELTA_BITSHIFT,
    "exact": DELTA_EXACT,
}
_DELTA_REVERSE = {v: k for k, v in DELTA_NAMES.items()}

_LNS_FORMATS = {n: f for n, f in FORMATS.items() if isinstance(f, LNSFormat)}


def _bad_value(key, got, valid):
    return ValueError(
        f"invalid {key}={got!r}; valid values: {', '.join(map(str, valid))}")


@dataclasses.dataclass(frozen=True)
class ReduceSpec:
    """Data-parallel gradient-reduction semantics (the ⊞ contract).

    ``mode="boxplus"`` is the deterministic log-domain schedule — the
    canonical segmentation of the global batch into ``grad_segments``
    contiguous equal segments plus a device-count-independent ⊞ combine
    (``schedule``); ``mode="float-psum"`` is the fast decode→psum→encode
    escape hatch (not bit-stable across device counts).
    ``grad_segments=0`` resolves to the device count at execution time.
    """

    mode: str = "boxplus"            # one of REDUCE_MODES
    grad_segments: int = 0           # 0 → device count
    schedule: str = "sequential"     # one of REDUCE_SCHEDULES

    def __post_init__(self):
        if self.mode not in REDUCE_MODES:
            raise _bad_value("reduce.mode", self.mode, REDUCE_MODES)
        if self.schedule not in REDUCE_SCHEDULES:
            raise _bad_value("reduce.schedule", self.schedule,
                             REDUCE_SCHEDULES)
        if self.grad_segments < 0:
            raise _bad_value("reduce.grad_segments", self.grad_segments,
                             ("any integer >= 0",))

    def with_(self, **kw) -> "ReduceSpec":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class NumericsSpec:
    """One frozen descriptor of the approximate arithmetic.

    Field ↔ serialized-key mapping (``parse`` / ``str``):

    ======================  =======================  =====================
    field                   key                      values
    ======================  =======================  =====================
    ``fmt``                 ``fmt``                  ``none`` | lns16 | lns12 | lns21
    ``delta_spec``          ``delta``                ``none`` | lut20 | lut640 |
                                                     bitshift | exact | ``lut:<d_max>:<r>``
    ``quantize``            ``quantize``             ``none`` or ``+``-joined subset
                                                     of params/acts/grads
    ``compute_dtype``       ``compute_dtype``        float32 | bfloat16 | float16
    ``backend``             ``backend``              emulate | pallas
    ``interpret``           ``interpret``            auto | on | off
    ``blocks``              ``blocks``               default | auto | ``<M>x<N>x<K>``
    ``metrics``             ``metrics``              off | counters | full
    ``reduce.mode``         ``reduce.mode``          boxplus | float-psum
    ``reduce.grad_segments``  ``reduce.grad_segments``  int >= 0
    ``reduce.schedule``     ``reduce.schedule``      sequential | tree
    ======================  =======================  =====================

    Hashable and usable as a jit static argument; ``with_`` produces a
    validated copy (dotted ``reduce.*`` keys update the nested spec).
    """

    fmt: Optional[LNSFormat] = None
    delta_spec: Optional[DeltaSpec] = None
    quantize: str = ""               # canonical '+'-joined QUANTIZE_AXES subset
    compute_dtype: str = "bfloat16"
    backend: str = "emulate"         # one of core.lns.MATMUL_BACKENDS
    interpret: str = "auto"          # one of INTERPRET_MODES
    blocks: str = "default"          # one of BLOCK_MODES (kernel tiling)
    metrics: str = "counters"        # one of METRICS_MODES (telemetry)
    reduce: ReduceSpec = ReduceSpec()

    def __post_init__(self):
        if self.backend not in MATMUL_BACKENDS:
            raise _bad_value("backend", self.backend, MATMUL_BACKENDS)
        if self.interpret not in INTERPRET_MODES:
            raise _bad_value("interpret", self.interpret, INTERPRET_MODES)
        if self.blocks not in ("default", "auto"):
            parse_blocks(self.blocks)  # raises with the valid forms
        if self.metrics not in METRICS_MODES:
            raise _bad_value("metrics", self.metrics, METRICS_MODES)
        if self.compute_dtype not in COMPUTE_DTYPES:
            raise _bad_value("compute_dtype", self.compute_dtype,
                             COMPUTE_DTYPES)
        # Normalize quantize to canonical order, then validate.  Any
        # subset of QUANTIZE_AXES is legal; the error lists all of them.
        toks = [t for t in self.quantize.split("+") if t]
        for t in toks:
            if t not in QUANTIZE_AXES:
                subsets = ["none"] + [
                    "+".join(a for i, a in enumerate(QUANTIZE_AXES)
                             if mask >> i & 1)
                    for mask in range(1, 1 << len(QUANTIZE_AXES))]
                raise _bad_value("quantize", self.quantize, subsets)
        object.__setattr__(
            self, "quantize",
            "+".join(a for a in QUANTIZE_AXES if a in toks))
        if self.quantize and self.fmt is None:
            raise ValueError(
                f"quantize={self.quantize!r} requires an LNS fmt; valid "
                f"fmt values: {', '.join(sorted(_LNS_FORMATS))}")
        if self.quantize_grads and self.delta_spec is None:
            raise ValueError(
                "quantize='...+grads' (end-to-end log-domain training) "
                "requires a delta spec; valid delta values: "
                + ", ".join(sorted(DELTA_NAMES)) + ", lut:<d_max>:<r>")
        if self.delta_spec is not None and self.fmt is None:
            raise ValueError(
                "a delta spec (⊞-MAC path) requires an LNS fmt; valid "
                f"fmt values: {', '.join(sorted(_LNS_FORMATS))}")

    # -- derived views ------------------------------------------------------
    @property
    def quantize_params(self) -> bool:
        return "params" in self.quantize.split("+")

    @property
    def quantize_acts(self) -> bool:
        return "acts" in self.quantize.split("+")

    @property
    def quantize_grads(self) -> bool:
        """End-to-end log-domain gradients (the ⊞-MAC backward path)."""
        return "grads" in self.quantize.split("+")

    # Legacy NumericsPolicy field names, for call sites written against
    # the pre-spec API.
    @property
    def lns_grad(self) -> bool:
        return self.quantize_grads

    @property
    def exact_spec(self) -> Optional[DeltaSpec]:
        return self.delta_spec

    @property
    def interpret_flag(self) -> Optional[bool]:
        """The tri-state mapped to ``LNSMatmulBackend.interpret``."""
        return {"auto": None, "on": True, "off": False}[self.interpret]

    # -- overrides ----------------------------------------------------------
    def with_(self, **kw) -> "NumericsSpec":
        """Validated copy with overrides; ``reduce.*`` keys nest.

        ``spec.with_(backend="pallas")`` or
        ``spec.with_(**{"reduce.mode": "float-psum"})``.  Unknown fields
        and invalid values raise with the valid-values list.
        """
        names = {f.name for f in dataclasses.fields(self)}
        flat, reduce_kw = {}, {}
        for k, v in kw.items():
            if k.startswith("reduce."):
                sub = k.split(".", 1)[1]
                if sub not in {f.name for f in dataclasses.fields(ReduceSpec)}:
                    raise _bad_value(
                        "override key", k,
                        tuple(f"reduce.{f.name}"
                              for f in dataclasses.fields(ReduceSpec)))
                reduce_kw[sub] = v
            elif k in names:
                flat[k] = v
            else:
                raise _bad_value(
                    "override key", k,
                    tuple(sorted(names))
                    + tuple(f"reduce.{f.name}"
                            for f in dataclasses.fields(ReduceSpec)))
        if reduce_kw:
            base = flat.get("reduce", self.reduce)
            flat["reduce"] = dataclasses.replace(base, **reduce_kw)
        return dataclasses.replace(self, **flat)

    # -- resolution ---------------------------------------------------------
    def runtime(self, block_m: int = 128, block_n: int = 128,
                block_k: int = 128) -> "LNSRuntime":
        """Resolve this spec once into a cached :class:`LNSRuntime`."""
        return _cached_runtime(self, block_m, block_n, block_k)

    # -- serialization ------------------------------------------------------
    def _flat(self) -> dict:
        """Serialized ``key → value-string`` view (parse's inverse)."""
        return {
            "fmt": self.fmt.name if self.fmt is not None else "none",
            "delta": _delta_to_str(self.delta_spec),
            "quantize": self.quantize or "none",
            "compute_dtype": self.compute_dtype,
            "backend": self.backend,
            "interpret": self.interpret,
            "blocks": self.blocks,
            "metrics": self.metrics,
            "reduce.mode": self.reduce.mode,
            "reduce.grad_segments": str(self.reduce.grad_segments),
            "reduce.schedule": self.reduce.schedule,
        }

    def __str__(self) -> str:
        exact = _alias_reverse().get(self)
        if exact is not None:
            return exact
        # Nearest registry alias + sorted overrides: lossless by
        # construction, and stable (registry order breaks ties).
        mine = self._flat()
        best_name, best_diff = None, None
        for name, spec in ALIASES.items():
            theirs = spec._flat()
            diff = {k: v for k, v in mine.items() if theirs[k] != v}
            if best_diff is None or len(diff) < len(best_diff):
                best_name, best_diff = name, diff
        return best_name + "".join(
            f",{k}={best_diff[k]}" for k in sorted(best_diff))

    @staticmethod
    def explicit_keys(text: "str | NumericsSpec") -> frozenset:
        """The ``key=value`` keys a spec string explicitly mentions.

        Tokenized exactly like :meth:`parse` (whitespace-tolerant), so
        "was this axis requested or is it an alias default?" is answered
        at the parse layer instead of by substring sniffing.  A
        ``NumericsSpec`` object (already canonical) reports the keys its
        ``str()`` form carries.
        """
        if isinstance(text, NumericsSpec):
            text = str(text)
        return frozenset(
            tok.split("=", 1)[0].strip()
            for tok in str(text).split(",") if "=" in tok)

    @staticmethod
    def parse(text: "str | NumericsSpec") -> "NumericsSpec":
        """Parse an alias, a ``key=value`` list, or alias + overrides.

        ``"lns16-train-pallas"``, ``"lns16-train-emulate,backend=pallas"``
        and ``"fmt=lns16,delta=lut20,quantize=params+acts+grads,
        compute_dtype=float32,backend=pallas"`` all resolve to the same
        spec.  Unknown aliases, keys, and values raise ``ValueError``
        listing the valid choices.  Already-parsed specs pass through.
        """
        if isinstance(text, NumericsSpec):
            return text
        return _parse_cached(str(text))


def _delta_to_str(d: Optional[DeltaSpec]) -> str:
    if d is None:
        return "none"
    named = _DELTA_REVERSE.get(d)
    if named is not None:
        return named
    if d.kind == "lut":
        # repr() is the shortest exact float representation, so the
        # round-trip stays lossless for any LUT parameters (%g would
        # truncate e.g. r=1/3 to 6 significant digits).
        return f"lut:{d.d_max!r}:{d.r!r}"
    return d.kind  # 'bitshift' / 'exact' with non-default (unused) d_max/r


def _delta_from_str(s: str) -> Optional[DeltaSpec]:
    if s == "none":
        return None
    if s in DELTA_NAMES:
        return DELTA_NAMES[s]
    if s.startswith("lut:"):
        try:
            _, d_max, r = s.split(":")
            return DeltaSpec(kind="lut", d_max=float(d_max), r=float(r))
        except ValueError:
            pass
    raise _bad_value("delta", s,
                     ("none",) + tuple(sorted(DELTA_NAMES))
                     + ("lut:<d_max>:<r>",))


def _fmt_from_str(s: str) -> Optional[LNSFormat]:
    if s == "none":
        return None
    if s in _LNS_FORMATS:
        return _LNS_FORMATS[s]
    raise _bad_value("fmt", s, ("none",) + tuple(sorted(_LNS_FORMATS)))


_PARSE_KEYS = ("fmt", "delta", "quantize", "compute_dtype", "backend",
               "interpret", "blocks", "metrics", "reduce.mode",
               "reduce.grad_segments", "reduce.schedule")


def override_from_kv(key: str, value: str):
    """Map one serialized ``key``/``value`` pair to a ``with_`` override.

    The single decode point for every serialized-spec surface: the spec
    parser and the :class:`~repro.core.plan.NumericsPlan` rule parser both
    route through it, so plan overrides accept exactly the vocabulary spec
    strings do.  Returns ``(field_name, typed_value)``; unknown keys and
    values raise with the valid-values list.
    """
    if key not in _PARSE_KEYS:
        raise _bad_value("spec key", key, _PARSE_KEYS)
    if key == "fmt":
        return "fmt", _fmt_from_str(value)
    if key == "delta":
        return "delta_spec", _delta_from_str(value)
    if key == "quantize":
        return "quantize", "" if value == "none" else value
    if key == "reduce.grad_segments":
        try:
            return key, int(value)
        except ValueError:
            raise _bad_value(key, value, ("any integer >= 0",)) from None
    return key, value


def apply_kv_overrides(spec: NumericsSpec, items) -> NumericsSpec:
    """Apply serialized ``(key, value)`` string pairs onto ``spec``."""
    overrides = dict(override_from_kv(k, v) for k, v in items)
    return spec.with_(**overrides) if overrides else spec


@functools.lru_cache(maxsize=None)
def _parse_cached(text: str) -> NumericsSpec:
    tokens = [t.strip() for t in text.split(",") if t.strip()]
    if not tokens:
        raise ValueError(
            f"empty numerics spec; pass an alias ({', '.join(ALIASES)}) "
            f"or key=value pairs ({', '.join(_PARSE_KEYS)})")
    if "=" in tokens[0]:
        spec = NumericsSpec()
    else:
        alias = tokens.pop(0)
        if alias not in ALIASES:
            raise ValueError(
                f"unknown numerics alias {alias!r}; "
                f"have {sorted(ALIASES)} (or key=value overrides: "
                f"{', '.join(_PARSE_KEYS)})")
        spec = ALIASES[alias]
    kv = []
    for tok in tokens:
        if "=" not in tok:
            raise ValueError(
                f"expected key=value after the alias, got {tok!r}; "
                f"valid keys: {', '.join(_PARSE_KEYS)}")
        kv.append(tuple(p.strip() for p in tok.split("=", 1)))
    return apply_kv_overrides(spec, kv)


# ------------------------------------------------------------------------
# Alias registry (the old stringly-typed POLICIES table, now data)
# ------------------------------------------------------------------------

#: Name → spec.  These are the *same* nine configurations the repo grew as
#: ``NumericsPolicy`` entries; the names stay valid everywhere a numerics
#: string is accepted, and ``str()`` canonicalizes back onto them.  New
#: combinations need no new alias — any spec serializes as nearest-alias +
#: overrides.
ALIASES = {
    "fp32": NumericsSpec(compute_dtype="float32"),
    "bf16": NumericsSpec(compute_dtype="bfloat16"),
    "lns16-qat": NumericsSpec(fmt=LNS16, quantize="params+acts"),
    "lns12-qat": NumericsSpec(fmt=LNS12, quantize="params+acts"),
    "lns16-w-only": NumericsSpec(fmt=LNS16, quantize="params"),
    "lns16-exact": NumericsSpec(
        fmt=LNS16, quantize="params+acts", delta_spec=DELTA_DEFAULT,
        compute_dtype="float32"),
    # Same arithmetic, forward matmuls on the Pallas kernel path via the
    # LNSMatmulBackend dispatcher (batched serving on the kernels).  NOTE:
    # the dispatcher runs the *sequential* MAC order; 'lns16-exact' keeps
    # the pairwise-tree emulation order of lns_dot_exact — both are valid
    # paper arithmetic, so the two differ by (bounded) approximation
    # reordering, not semantics.
    "lns16-exact-pallas": NumericsSpec(
        fmt=LNS16, quantize="params+acts", delta_spec=DELTA_DEFAULT,
        compute_dtype="float32", backend="pallas"),
    # End-to-end log-domain training: gradients run the transposed ⊞-MACs
    # (dX = dY ⊞ Wᵀ, dW = Xᵀ ⊞ dY) instead of straight-through float
    # matmuls — the hardware-shaped path of Hamad et al.
    "lns16-train-emulate": NumericsSpec(
        fmt=LNS16, quantize="params+acts+grads", delta_spec=DELTA_DEFAULT,
        compute_dtype="float32", backend="emulate"),
    "lns16-train-pallas": NumericsSpec(
        fmt=LNS16, quantize="params+acts+grads", delta_spec=DELTA_DEFAULT,
        compute_dtype="float32", backend="pallas"),
}


@functools.lru_cache(maxsize=1)
def _alias_reverse() -> dict:
    return {spec: name for name, spec in ALIASES.items()}


# ------------------------------------------------------------------------
# LNSRuntime — the spec resolved once
# ------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LNSRuntime:
    """A :class:`NumericsSpec` resolved into live execution objects.

    Frozen/hashable (usable as a jit static argument); the heavyweight
    members are cached:

    * :attr:`matmul` — the :class:`~repro.core.lns.LNSMatmulBackend` for
      the spec's (fmt, Δ, backend, interpret) at this runtime's block
      sizes: forward + all backward ⊞-MAC products and the segmented
      dW-partials emitter of the DP reduce.
    * :attr:`delta_engine` — the shared Δ engine for (Δ spec, fmt).
    * per-op policy behavior (:meth:`q_param` / :meth:`q_act` /
      :meth:`linear`) — what ``repro.nn`` layers call; bit-identical to
      the retired ``NumericsPolicy`` dispatch.
    * :meth:`dp_config` — the data-parallel reduce plan from
      ``spec.reduce``.

    Legacy ``NumericsPolicy`` attribute names (``param_lns`` /
    ``exact_spec`` / ``lns_grad`` / ``matmul_backend`` …) are provided so
    pre-spec call sites keep working unchanged.
    """

    spec: NumericsSpec
    block_m: int = 128
    block_n: int = 128
    block_k: int = 128

    # -- resolved members --------------------------------------------------
    @functools.cached_property
    def matmul(self) -> LNSMatmulBackend:
        s = self.spec
        if s.fmt is None or s.delta_spec is None:
            raise ValueError(
                f"spec {str(s)!r} has no ⊞-MAC path (needs fmt + delta); "
                f"set e.g. fmt=lns16,delta=lut20")
        # The spec's blocks axis wins over this runtime's tile sizes: an
        # explicit "MxNxK" pins them, "auto" defers to the autotuner per
        # op+shape at launch (kernels/autotune.py).
        bm, bn, bk, mode = resolve_blocks_arg(
            s.blocks, self.block_m, self.block_n, self.block_k)
        return LNSMatmulBackend(
            fmt=s.fmt, spec=s.delta_spec, backend=s.backend,
            block_m=bm, block_n=bn, block_k=bk, blocks=mode,
            interpret=s.interpret_flag)

    @functools.cached_property
    def delta_engine(self):
        s = self.spec
        if s.fmt is None or s.delta_spec is None:
            raise ValueError(
                f"spec {str(s)!r} has no Δ engine (needs fmt + delta)")
        return _cached_engine(s.delta_spec, s.fmt)

    def dp_config(self, num_devices: int = 1, **kw):
        """The data-parallel reduce plan: a ``DPConfig`` from this spec."""
        from ..distributed.lns_dp import DPConfig
        return DPConfig(num_devices=num_devices, reduce=self.spec.reduce,
                        **kw)

    # -- per-op numerics-policy behavior (what repro.nn layers call) -------
    @property
    def name(self) -> str:
        return str(self.spec)

    @property
    def lane(self) -> str:
        """The *resolved* execution lane of this runtime's matmuls, for
        metrics rows: a plan may say ``backend=pallas,interpret=auto`` —
        this answers what actually runs ("emulate", "pallas-hw",
        "pallas-interpret", or "float-<dtype>" off the ⊞-MAC path)."""
        s = self.spec
        if s.delta_spec is None or s.fmt is None:
            return f"float-{s.compute_dtype}"
        if s.backend == "emulate":
            return "emulate"
        return "pallas-interpret" if self.matmul._interp() else "pallas-hw"

    @property
    def dtype(self):
        return jnp.dtype(self.spec.compute_dtype)

    def q_param(self, w):
        if self.spec.quantize_params:
            from .qat import lns_quantize_ste
            w = lns_quantize_ste(w, self.spec.fmt)
        return w.astype(self.dtype)

    def q_act(self, x):
        if self.spec.quantize_acts:
            from .qat import lns_quantize_ste
            x = lns_quantize_ste(x, self.spec.fmt)
        return x.astype(self.dtype)

    def linear(self, x, w):
        """Contract x's last dim against w's first dim under this spec.

        Dispatch is bit-identical to the pre-spec ``NumericsPolicy``:
        Δ-spec'd numerics run the ⊞-MAC path (end-to-end log-domain
        gradients when ``quantize`` includes grads, dispatcher/emulation
        forward otherwise); plain quantized numerics run STE-quantized
        float matmuls on the MXU dtype.
        """
        with self._tapping(op="linear") as observe:
            s = self.spec
            if s.delta_spec is not None:
                if s.quantize_grads:
                    # Forward AND cotangent matmuls on the ⊞-MAC path
                    # (custom_vjp boundary in kernels/lns_matmul/ops.py);
                    # lazy import keeps core importable without the
                    # kernels package.
                    from ..kernels.lns_matmul import lns_matmul_trainable
                    out = lns_matmul_trainable(x, w, self.matmul)
                elif s.backend != "emulate":
                    # Forward-only on the dispatcher (Pallas kernels off
                    # the emulation): the batched-serving path.
                    from .qat import lns_dot_dispatch
                    out = lns_dot_dispatch(x, w, self.matmul)
                else:
                    from .qat import lns_dot_exact
                    out = lns_dot_exact(x, w, s.fmt, s.delta_spec)
            else:
                out = jnp.matmul(self.q_act(x), self.q_param(w))
        observe(out)
        return out

    def grouped_linear(self, x, w, sizes):
        """Rows ``x`` (M, K) sorted by group against per-group ``w``
        (G, K, N), ``sizes`` (G,) rows per group → (M, N); rows past
        ``sum(sizes)`` give 0.

        On the end-to-end LNS training path the grouped ⊞-MAC kernels
        run forward and both cotangent products
        (``kernels/lns_matmul/grouped.py``); every other spec runs the
        grouped float matmul on :meth:`q_act` / :meth:`q_param` operands.
        """
        s = self.spec
        if s.delta_spec is not None and s.quantize_grads:
            from ..kernels.lns_matmul import lns_gmm_trainable
            return lns_gmm_trainable(x, w, sizes, self.matmul)
        return jax.lax.ragged_dot(self.q_act(x), self.q_param(w),
                                  sizes.astype(jnp.int32))

    def linear_infer(self, x, w):
        """Forward-only :meth:`linear` for serving (decode / prefill).

        Bit-identical to :meth:`linear`'s forward on every spec, but
        Δ-spec'd numerics with a kernel path route through the *fused*
        forward-epilogue backend surface
        (:meth:`~repro.core.lns.LNSMatmulBackend.matmul_fused` — one
        flush-time launch, no custom_vjp machinery resident).  The
        emulate-backend exact mode keeps :meth:`linear`'s pairwise-tree
        ``lns_dot_exact`` (there is no kernel to fuse, and changing the
        reduction order would change results).  No gradient path —
        training must use :meth:`linear`.
        """
        s = self.spec
        if s.delta_spec is not None and (s.quantize_grads
                                         or s.backend != "emulate"):
            with self._tapping(op="linear_infer") as observe:
                from .qat import lns_dot_fused
                out = lns_dot_fused(x, w, self.matmul)
            observe(out)
            return out
        if s.delta_spec is None:
            with self._tapping(op="linear_infer") as observe:
                out = jnp.matmul(self.q_act(x), self.q_param(w))
            observe(out)
            return out
        return self.linear(x, w)  # observed under op="linear"

    @contextlib.contextmanager
    def _tapping(self, *, op: str):
        """Scope-gated float-view health tap on a linear output.

        Yields an ``observe(out)`` callback and, while active, *suspends*
        collection — the dispatched implementations contain inner traces
        (``custom_vjp`` rules, STE quantizers, jitted kernel wrappers)
        where a core-op tap would capture an inner tracer on the
        Python-side collector and leak it.  The linear-level output tap
        is the per-layer signal instead.  Fires only when this spec opted
        in (``metrics != "off"``), a collector is live, AND an ambient
        ``obs.scope`` names the layer (scopes are never set inside
        grad-of regions by contract).  Pure reads; never changes results.
        """
        from ..obs import metrics as _obs
        if self.spec.metrics == "off" or not _obs.scope_active():
            yield lambda out: None
            return
        with _obs.suspended():
            yield lambda out: _obs.observe_float(out, self.spec.fmt, op=op)

    @property
    def matmul_path(self) -> str:
        """Human-readable description of the path :meth:`linear` takes.

        Kept next to ``linear`` so the description cannot drift from the
        dispatch it documents (serving surfaces just forward it).
        """
        s = self.spec
        if s.delta_spec is None:
            return f"float XLA matmul ({s.compute_dtype})"
        if s.quantize_grads or s.backend != "emulate":
            return f"LNS ⊞-MAC via LNSMatmulBackend(backend='{s.backend}')"
        return "LNS ⊞-MAC via lns_dot_exact (emulated, pairwise-tree order)"

    @property
    def infer_path(self) -> str:
        """Description of the path :meth:`linear_infer` takes (serving)."""
        s = self.spec
        if s.delta_spec is None:
            return f"float XLA matmul ({s.compute_dtype})"
        if s.quantize_grads or s.backend != "emulate":
            return (f"LNS ⊞-MAC via matmul_fused "
                    f"(fused forward-epilogue surface, "
                    f"backend='{s.backend}')")
        return "LNS ⊞-MAC via lns_dot_exact (emulated, pairwise-tree order)"

    # -- legacy NumericsPolicy surface ------------------------------------
    @property
    def compute_dtype(self) -> str:
        return self.spec.compute_dtype

    @property
    def param_lns(self) -> Optional[LNSFormat]:
        return self.spec.fmt if self.spec.quantize_params else None

    @property
    def act_lns(self) -> Optional[LNSFormat]:
        return self.spec.fmt if self.spec.quantize_acts else None

    @property
    def exact_spec(self) -> Optional[DeltaSpec]:
        return self.spec.delta_spec

    @property
    def lns_grad(self) -> bool:
        return self.spec.quantize_grads

    @property
    def matmul_backend(self) -> str:
        return self.spec.backend


_RUNTIME_CACHE: dict = {}


def _cached_runtime(spec: NumericsSpec, block_m: int, block_n: int,
                    block_k: int) -> LNSRuntime:
    key = (spec, block_m, block_n, block_k)
    if key not in _RUNTIME_CACHE:
        _RUNTIME_CACHE[key] = LNSRuntime(spec, block_m, block_n, block_k)
    return _RUNTIME_CACHE[key]
