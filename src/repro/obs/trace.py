"""Step tracing: what the program names in a profiler trace, and host timers.

What is tagged, and where:

* **Kernel launches** — every ``pl.pallas_call`` passes
  ``metadata={"kind": ...}``, which lands in the custom call's
  ``frontend_attributes={kernel_metadata={...}}`` and so in the name of
  the launch's event on the device's ``XLA Ops`` line.  ``kind`` is one of
  ``fwd`` / ``dx`` / ``dw`` / ``fused_fwd`` / ``dw_update`` /
  ``dw_partials`` (``kernels/lns_matmul/lns_matmul.py: _launch_mac``,
  which adds the launch's logical extents ``r`` / ``c`` / ``ct`` and the
  padded ones ``rp`` / ``cp`` / ``ctp``), ``fused_update``
  (``kernels/lns_matmul/update.py``) and ``boxsum``
  (``kernels/lns_boxsum/lns_boxsum.py``).  Every launch that evaluates
  Δ± also names how, under ``delta``: ``lut-gather``, ``lut-chain``,
  ``bitshift`` or ``exact`` (``lns_matmul.delta_path``).
* **Train-step entry points** — ``paper/mlp.py: LNSMLP.train_step`` and
  ``distributed/lns_dp.py: LNSDataParallelMLP.train_step`` (through
  :func:`host_span`) open the host span ``repro.train_step`` around their
  jitted call: argument handling, the batch's host-to-device copies,
  output allocation and the launch nest under it on the ``/host:CPU``
  plane, on the device trace's clock.  ``LNSMLP``'s span carries the
  argument ``donated`` (1 where the step reused the buffers of the state
  handed back to it).
* **Launch loops** — ``launch/train.py`` and ``paper/training.py:
  run_experiment`` wrap each iteration in
  ``StepTraceAnnotation("repro.train", step_num=...)`` (xprof's per-step
  view), and :class:`StepTimer` spans open a host span of their own name,
  so its ``perf_counter`` times and the trace mark the same boundaries.
* **Phases inside a jitted step** — :func:`phase_scope` is a
  ``jax.named_scope``: ``fwd`` / ``dx`` / ``dw`` / ``update`` in
  ``paper/mlp.py``, ``reduce`` in ``distributed/lns_dp.py`` with
  ``reduce/gather`` and ``reduce/fold`` in ``distributed/lns_reduce.py``,
  ``grad`` / ``update`` in ``train/step.py``.  Named scopes reach the HLO
  ops' ``op_name`` and xprof's op profile; they are not in the event stats
  that ``jax.profiler.ProfileData`` reads back, so a trace reader cannot
  split device time by them.

None of this changes results.  With no profiler session a host span costs
a C++ enabled-check and one Python frame.  :func:`maybe_profile` is the
one switch that records a trace (``launch/train.py --profile-dir``).
"""
from __future__ import annotations

import contextlib
import functools
import os
import time

import jax


def phase_scope(name):
    """Profiler-visible named scope; trace-safe, results unchanged."""
    return jax.named_scope(name)


def host_span(name):
    """Decorator: run the function inside the host span ``name``.

    Meant for jitted entry points: the span covers the whole dispatch of
    one call.  The name is fixed when decorating, so an untraced call
    formats nothing and reads no clock."""
    annotation = jax.profiler.TraceAnnotation

    def wrap(fn):
        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            with annotation(name):
                return fn(*args, **kwargs)
        return spanned
    return wrap


class StepTimer:
    """Named host-side monotonic timers with simple summaries; each span
    is also a host span of the same name in a profiler trace.  A span
    includes device time only if the caller waits for the device inside
    it (``block_until_ready`` or reading a host value), as the launch CLI
    does.

    >>> t = StepTimer()
    >>> with t.span("train.step"):
    ...     out = step_fn(...); jax.block_until_ready(out)
    >>> t.last("train.step")  # ms
    """

    def __init__(self):
        self._samples: dict = {}

    def record(self, name, ms):
        self._samples.setdefault(name, []).append(float(ms))

    @contextlib.contextmanager
    def span(self, name):
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.record(name, (time.perf_counter() - t0) * 1e3)

    def last(self, name):
        s = self._samples.get(name)
        return s[-1] if s else None

    def samples(self, name):
        return list(self._samples.get(name, ()))

    def summary(self, skip_first=0):
        """Per-name stats dict: count / mean_ms / p50_ms / best_ms.
        ``skip_first`` drops warmup (compile) samples from the stats of
        every series that has more than that many samples."""
        out = {}
        for name, s in sorted(self._samples.items()):
            body = s[skip_first:] if len(s) > skip_first else s
            srt = sorted(body)
            out[name] = {
                "count": len(s),
                "mean_ms": sum(body) / len(body),
                "p50_ms": srt[len(srt) // 2],
                "best_ms": srt[0],
            }
        return out


@contextlib.contextmanager
def maybe_profile(trace_dir=None):
    """Dump a jax.profiler trace of the enclosed region to ``trace_dir``;
    with no directory, a no-op context."""
    if not trace_dir:
        yield None
        return
    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir)
    try:
        yield trace_dir
    finally:
        jax.profiler.stop_trace()
