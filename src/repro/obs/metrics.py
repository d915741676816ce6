"""In-graph numerics counters: a JAX-safe side-channel for LNS health.

The collection model is **observer-only**: every counter is computed from
the *inputs or outputs* of an op with pure reads (comparisons + integer
sums) — the op's own arithmetic is never touched, so telemetry can never
change results.  Counters are traced int32 scalars accumulated on a
trace-time collector stack and returned as an extra output of a
metrics-enabled jitted entry point (the MLP models' ``train_step_metrics``).
The plain ``train_step`` never pushes a collector, so with collection off
the jitted graphs are byte-for-byte the ones this module never saw — a
true no-op, not a disabled branch.

Tap sites are **scope-gated**: instrumented core ops (``encode`` /
``convert_format`` / the fused-epilogue dispatch) only record when an
ambient ``scope(layer, op)`` is active, and scopes are only set from code
regions that are never traced under ``jax.grad`` / ``custom_vjp`` rules /
``lax.scan`` bodies / ``shard_map`` bodies — the places where capturing a
traced value on a Python-side stack would leak a tracer.  ``suspended()``
force-disables collection around such regions (the DP step wraps its
``shard_map`` call in it).

Counter vocabulary (all int32 element counts):

* ``elems`` / ``sat`` / ``zero``       — code-plane health of an LNS
  tensor: total elements, codes pinned at ``fmt.code_max`` (saturated at
  the format's exponent ceiling), and zero-sentinel codes.
* ``q_elems`` / ``q_sat`` / ``q_flush`` — float→LNS quantization (the
  ``encode`` path): elements whose rounded log-magnitude clipped at
  ``code_max``, and *nonzero* values flushed to the zero sentinel by
  underflow.
* ``convert_elems`` / ``convert_sat`` / ``convert_flush`` — the
  barrel-shift format crossing (``convert_format``): nonzero codes that
  saturated at / flushed out of the destination grid.
* ``dhist`` — int32 histogram (length ``len(DHIST_EDGES) + 1``) of the
  ``|d| = |X - Y|`` values entering the Δ engine during a sequential
  ⊞-MAC, in log2-magnitude buckets: Δ-LUT region occupancy.

Labels are ``"<layer>/<op>/<counter>"`` strings; repeated taps under one
label accumulate (``+``), so per-segment or per-call contributions sum.
This module deliberately imports nothing from ``repro.core`` — core ops
import *it*, and the only contract is duck-typed ``(code, sign)`` arrays
plus ``LNSFormat``-shaped attributes (``scale`` / ``code_max`` /
``zero_code`` / ``min_nonzero_code``).
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp

#: Pinned Δ-LUT occupancy bucket edges, in log2-magnitude units of |d|
#: (format-independent; converted to code units per format at tap time).
#: Buckets: [0,1) [1,2) [2,4) [4,8) [8,10) [10,∞) — the last bucket is
#: "beyond the paper LUT" (d ≥ d_max=10, where Δ± has decayed to 0 and
#: the engine returns the max operand unchanged).  tests/test_obs.py pins
#: these edges; changing them invalidates every committed dhist row.
DHIST_EDGES = (1.0, 2.0, 4.0, 8.0, 10.0)

# Trace-time state.  A ``None`` entry on the collector stack means
# "collection suspended" (shard_map/grad regions); enabled() is False.
_COLLECTORS: list = []
_SCOPES: list = []


class NumericsCollector:
    """Accumulates labeled traced int32 values during one jit trace."""

    def __init__(self):
        self._taps: dict = {}

    def add(self, label: str, value) -> None:
        prev = self._taps.get(label)
        self._taps[label] = value if prev is None else prev + value

    def taps(self) -> dict:
        """The accumulated ``label → int32 array`` dict (sorted keys, so
        the jit output treedef is deterministic)."""
        return {k: self._taps[k] for k in sorted(self._taps)}


def enabled() -> bool:
    """True iff a live (non-suspended) collector is on the stack."""
    return bool(_COLLECTORS) and _COLLECTORS[-1] is not None


def scope_active() -> bool:
    """True iff collection is enabled AND an ambient scope is set."""
    return enabled() and bool(_SCOPES)


def current_scope():
    """The innermost ambient ``(layer, op)``, or ``(None, None)``."""
    return _SCOPES[-1] if _SCOPES else (None, None)


@contextlib.contextmanager
def collecting():
    """Push a fresh collector; yields it.  Use inside the jitted body of a
    metrics-enabled entry point and return ``collector.taps()`` alongside
    the step outputs — the taps are tracers of the same trace."""
    col = NumericsCollector()
    _COLLECTORS.append(col)
    try:
        yield col
    finally:
        _COLLECTORS.pop()


@contextlib.contextmanager
def suspended():
    """Force-disable collection for a region (shard_map / custom_vjp /
    scan bodies): inner taps would capture tracers from an inner trace
    on the Python-side collector — a leak, not telemetry."""
    _COLLECTORS.append(None)
    try:
        yield
    finally:
        _COLLECTORS.pop()


@contextlib.contextmanager
def scope(layer=None, op=None):
    """Set the ambient (layer, op) label for scope-gated taps.  ``None``
    inherits the enclosing scope's value."""
    cl, co = current_scope()
    _SCOPES.append((layer if layer is not None else cl,
                    op if op is not None else co))
    try:
        yield
    finally:
        _SCOPES.pop()


def _label(counter: str, layer, op) -> str:
    cl, co = current_scope()
    layer = layer if layer is not None else (cl or "default")
    op = op if op is not None else (co or "op")
    return f"{layer}/{op}/{counter}"


def add_taps(taps: dict) -> None:
    """Merge ``label → value`` taps collected in an inner trace (and
    returned from it as outputs) into the live collector."""
    if enabled():
        for label, value in taps.items():
            _COLLECTORS[-1].add(label, value)


def tap(counter: str, value, *, layer=None, op=None) -> None:
    """Record one labeled int32 value (no-op unless collection is on)."""
    if enabled():
        _COLLECTORS[-1].add(_label(counter, layer, op),
                            jnp.asarray(value, jnp.int32))


def _count(mask) -> jnp.ndarray:
    return jnp.sum(mask, dtype=jnp.int32)


def observe_codes(a, fmt, *, layer=None, op=None) -> None:
    """Code-plane health of an LNS tensor: elems / sat / zero.

    Pure reads of ``a.code`` — the tensor flows on unchanged.
    """
    if not enabled():
        return
    tap("elems", a.code.size, layer=layer, op=op)
    tap("sat", _count(a.code == fmt.code_max), layer=layer, op=op)
    tap("zero", _count(a.code == fmt.zero_code), layer=layer, op=op)


def observe_quantize(raw_code, nonzero_mask, fmt, *, layer=None,
                     op=None) -> None:
    """Float→LNS quantization health, from the *pre-clip* rounded code.

    ``raw_code`` is ``round(log2|v| · 2^qf)`` before saturation (garbage
    on zero lanes — masked by ``nonzero_mask``).  Called by
    ``core.lns.encode`` under an ambient scope.
    """
    if not scope_active():
        return
    tap("q_elems", raw_code.size, layer=layer, op=op)
    tap("q_sat", _count(nonzero_mask & (raw_code > fmt.code_max)),
        layer=layer, op=op)
    tap("q_flush", _count(nonzero_mask & (raw_code < fmt.min_nonzero_code)),
        layer=layer, op=op)


def observe_convert(src_nonzero, raw_code, dst_fmt, *, layer=None,
                    op=None) -> None:
    """Format-crossing health: the barrel-shifted ``raw_code`` (pre-clip)
    against the destination grid, over lanes that were nonzero in the
    source.  Called by ``core.lns.convert_format`` under a scope."""
    if not scope_active():
        return
    tap("convert_elems", raw_code.size, layer=layer, op=op)
    tap("convert_sat", _count(src_nonzero & (raw_code > dst_fmt.code_max)),
        layer=layer, op=op)
    tap("convert_flush",
        _count(src_nonzero & (raw_code < dst_fmt.min_nonzero_code)),
        layer=layer, op=op)


def observe_float(v, fmt, *, layer=None, op=None) -> None:
    """Health of a *float-view* tensor against an LNS format (the
    ``LNSRuntime.linear``/``linear_infer`` outputs of the QAT stack):
    exact zeros, and magnitudes at/above the format's representable
    ceiling.  ``fmt=None`` records only ``elems``/``zero``."""
    if not enabled():
        return
    mag = jnp.abs(v)
    tap("elems", mag.size, layer=layer, op=op)
    tap("zero", _count(mag == 0), layer=layer, op=op)
    if fmt is not None:
        ceil = jnp.float32(2.0) ** (jnp.float32(fmt.code_max) / fmt.scale)
        tap("sat", _count(mag >= ceil), layer=layer, op=op)


def dhist_edges_codes(fmt) -> jnp.ndarray:
    """The pinned DHIST_EDGES on ``fmt``'s integer code grid."""
    return jnp.asarray([int(round(e * fmt.scale)) for e in DHIST_EDGES],
                       jnp.int32)
