"""Guardrails: detectors over the obs numerics taps wired to recovery.

PR 7's telemetry gave the training loop sensors (saturation counters,
quantize-flush counters, the loss readout); this module makes something
*act* on them.  Three detectors — saturation storm, zero-flush spike,
nonfinite/spiking loss — feed three recovery policies:

* **Step rollback** from a bounded in-memory :class:`SnapshotRing` of
  host-side state copies (weight codes + ⊞-momentum + rng), the cheap
  undo for transient faults (a bit-flip storm inside one step window).
* **Format widening**: a persistent saturation storm in a narrow layer
  becomes a :class:`~repro.core.plan.NumericsPlan` override
  (``plan.with_rule(layer, fmt=<wider>)``) — the model is rebuilt under
  the widened plan and the layer's codes are converted with the exact
  integer barrel shifts of :func:`~repro.core.lns.convert_format`, so
  widening itself never loses information.  The override is logged
  through obs (``guard.widened`` counter + the event log carries both
  plan strings).
* **DP device-drop recovery** (:func:`recover_segment_partials`): the
  canonical device-count-independent segmentation (``lns_reduce``) makes
  each segment partial a pure function of its own batch rows, so a lost
  device's segments can be *recomputed* and spliced into the surviving
  partial stack; the fixed-schedule ⊞ combine then yields weight codes
  **bit-identical** to a fresh run at the surviving device count — the
  contract ``tests/test_resil.py`` pins.

Everything here is host-side policy around the jitted step: the step
functions themselves stay pure and the guardrails never fork the traced
arithmetic (disabled guardrails ⇒ the exact train_step graphs of HEAD).
"""
from __future__ import annotations

import collections
import dataclasses
import math
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import convert_format
from ..core.plan import NumericsPlan
from ..obs.registry import MetricsRegistry
from . import inject as _inj


@dataclasses.dataclass(frozen=True)
class GuardConfig:
    """Detector thresholds + recovery policy switches.

    The all-off config (``GuardConfig(rollback=False, widen=False)``)
    reduces :class:`GuardedTrainer` to a plain metrics loop — same
    trained codes as driving ``train_step_metrics`` by hand.
    """

    sat_frac: float = 0.25      # saturations / elems per layer → storm
    flush_frac: float = 0.60    # zero-flushes (or q_flush) / elems → spike
    loss_abs: float = 1.0e4     # absolute loss ceiling
    loss_spike: float = 10.0    # × median of recent losses
    ring: int = 4               # snapshots kept (bounded memory)
    snapshot_every: int = 1     # push cadence in steps
    rollback: bool = True
    widen: bool = True
    widen_fmt: str = "lns16"    # target format of the widening override
    cooldown: int = 2           # steps to hold fire after a recovery


@dataclasses.dataclass(frozen=True)
class Alert:
    kind: str            # 'saturation-storm' | 'zero-flush-spike' |
                         # 'nonfinite-loss' | 'loss-spike'
    layer: Optional[str]  # None for loss alerts (not layer-attributable)
    value: float         # the offending fraction / loss value
    step: int


class SnapshotRing:
    """Bounded ring of host-side training-state snapshots.

    Entries are ``jax.device_get`` copies (LNSArray pytrees with numpy
    leaves), so a rollback is immune to any later in-place device-side
    donation and costs no device memory.  ``rng`` rides along for steps
    that thread one (the paper MLP step is rng-free; the slot keeps the
    snapshot format stable for steps that are not).
    """

    def __init__(self, capacity: int):
        self._ring = collections.deque(maxlen=max(1, capacity))

    def push(self, step: int, params, momentum=None, rng=None):
        self._ring.append(
            (step, jax.device_get((params, momentum, rng))))

    def latest(self):
        """``(step, (params, momentum, rng))`` of the newest snapshot, or
        ``None`` when empty."""
        return self._ring[-1] if self._ring else None

    def __len__(self):
        return len(self._ring)


def detect(taps: dict, loss: float, cfg: GuardConfig,
           recent_losses=(), step: int = 0) -> List[Alert]:
    """Run the three detectors over one step's taps + loss readout.

    ``taps`` is the ``"layer/op/counter"`` dict a ``*_metrics`` entry
    point returns.  Saturation and flush fractions are computed per
    (layer, op) pair against that pair's own ``elems``/``q_elems``
    denominator, and the *worst* offending pair per layer raises the
    alert — detectors read the raw taps, so they see exactly what the
    arithmetic saw (including injected faults: detection latency in the
    drills is measured in steps from injection to the first alert).
    """
    alerts: List[Alert] = []
    worst_sat: dict = {}
    worst_flush: dict = {}
    for label, v in taps.items():
        parts = label.split("/")
        if len(parts) != 3:
            continue
        layer, op, counter = parts
        v = np.asarray(v)
        if v.ndim != 0:
            continue  # dhist buckets etc.
        v = int(v)
        if counter == "sat":
            denom = int(np.asarray(taps.get(f"{layer}/{op}/elems", 0)))
            if denom:
                frac = v / denom
                if frac > worst_sat.get(layer, 0.0):
                    worst_sat[layer] = frac
        elif counter in ("zero", "q_flush"):
            dkey = f"{layer}/{op}/" + (
                "elems" if counter == "zero" else "q_elems")
            denom = int(np.asarray(taps.get(dkey, 0)))
            if denom:
                frac = v / denom
                if frac > worst_flush.get(layer, 0.0):
                    worst_flush[layer] = frac
    for layer in sorted(worst_sat):
        if worst_sat[layer] >= cfg.sat_frac:
            alerts.append(Alert("saturation-storm", layer,
                                worst_sat[layer], step))
    for layer in sorted(worst_flush):
        if worst_flush[layer] >= cfg.flush_frac:
            alerts.append(Alert("zero-flush-spike", layer,
                                worst_flush[layer], step))
    loss = float(loss)
    if not math.isfinite(loss):
        alerts.append(Alert("nonfinite-loss", None, loss, step))
    else:
        if loss > cfg.loss_abs:
            alerts.append(Alert("loss-spike", None, loss, step))
        elif recent_losses:
            med = float(np.median(np.asarray(recent_losses)))
            if med > 0 and loss > cfg.loss_spike * med:
                alerts.append(Alert("loss-spike", None, loss, step))
    return alerts


def _inner(model):
    """The per-layer LNSMLP view of a (possibly DP-wrapped) model."""
    return getattr(model, "inner", model)


class GuardedTrainer:
    """Host-side training loop wrapper: snapshot → step → detect → act.

    Drives the model's ``train_step_metrics`` (keyed by the step number,
    which arms the model's :class:`~repro.resil.inject.FaultPlan` when it
    carries one), feeds the taps + loss readout to :func:`detect`, and
    applies the configured recovery:

    * loss alerts (nonfinite / spike) → **rollback** to the most recent
      snapshot (which is this step's *pre*-state at
      ``snapshot_every=1`` — the damaged update is discarded);
    * layer alerts (saturation storm / flush spike) → **widen** the layer
      via a plan override (plus a rollback when enabled, so the widened
      format resumes from undamaged codes).

    A ``cooldown`` holds recovery off for a few steps afterwards so a
    fault window longer than one step cannot thrash the ring.  Every
    recovery is appended to :attr:`events` and counted in the registry
    (``guard.alerts`` / ``guard.rollbacks`` / ``guard.widened``).
    """

    def __init__(self, model, params, momentum=None, *,
                 guard: GuardConfig = GuardConfig(),
                 registry: Optional[MetricsRegistry] = None):
        self.model = model
        self.params = params
        self.momentum = momentum
        self.guard = guard
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self.ring = SnapshotRing(guard.ring)
        self.step_no = 0
        self.events: List[dict] = []
        self._cooldown = 0
        self._losses: collections.deque = collections.deque(maxlen=16)

    # -- one guarded step -------------------------------------------------
    def step(self, xb, yb) -> dict:
        g = self.guard
        if self.step_no % g.snapshot_every == 0:
            self.ring.push(self.step_no, self.params, self.momentum)
        model = self.model
        out, taps = model.train_step_metrics(
            self.params, xb, yb, self.momentum,
            step=jnp.int32(self.step_no))
        if self.momentum is None:
            new_params, loss = out
            new_mom = None
        else:
            new_params, new_mom, loss = out
        loss = float(loss)
        taps = {k: np.asarray(v) for k, v in taps.items()}
        self.registry.merge_numerics_taps(taps,
                                          lanes=_inner(model).lanes())
        alerts = []
        action = None
        if self._cooldown > 0:
            self._cooldown -= 1
        else:
            alerts = detect(taps, loss, g, recent_losses=self._losses,
                            step=self.step_no)
        if alerts:
            self.registry.counter_inc("guard.alerts", len(alerts))
            layer_alerts = [a for a in alerts if a.layer is not None]
            if g.widen and layer_alerts:
                widened = self._widen(layer_alerts[0].layer)
                if widened:
                    action = "widen"
            if g.rollback and len(self.ring):
                snap_step, (p, m, _rng) = self.ring.latest()
                new_params, new_mom = p, m
                self.registry.counter_inc("guard.rollbacks")
                action = f"{action}+rollback" if action else "rollback"
                self.events.append(dict(
                    step=self.step_no, action="rollback",
                    to_step=snap_step,
                    alerts=[dataclasses.asdict(a) for a in alerts]))
            if action:
                self._cooldown = g.cooldown
        else:
            self._losses.append(loss)
        self.params, self.momentum = new_params, new_mom
        self.step_no += 1
        return dict(step=self.step_no - 1, loss=loss, alerts=alerts,
                    action=action)

    # -- recovery: per-layer format widening ------------------------------
    def _widen(self, layer: str) -> bool:
        """Rebuild the model with ``layer`` widened to
        ``guard.widen_fmt``; convert that layer's codes exactly.  Returns
        False (no-op) when the layer is already at least that wide."""
        import dataclasses as _dc

        from ..core.formats import FORMATS
        from ..paper.mlp import PARAM_LAYER, make_mlp
        inner = _inner(self.model)
        old_fmt = inner.fmts[layer]
        new_fmt = FORMATS[self.guard.widen_fmt]
        if old_fmt.qi + old_fmt.qf >= new_fmt.qi + new_fmt.qf:
            return False
        old_plan = inner.plan
        new_plan = old_plan.with_rule(layer, fmt=self.guard.widen_fmt)
        cfg = _dc.replace(self.model.cfg, spec=new_plan)
        self.model = make_mlp("lns", cfg)
        for k, l in PARAM_LAYER.items():
            if l != layer:
                continue
            self.params = dict(self.params)
            self.params[k] = convert_format(self.params[k], old_fmt,
                                            new_fmt)
            if self.momentum is not None:
                self.momentum = dict(self.momentum)
                self.momentum[k] = convert_format(self.momentum[k],
                                                  old_fmt, new_fmt)
        self.registry.counter_inc("guard.widened", layer=layer)
        self.events.append(dict(
            step=self.step_no, action="widen", layer=layer,
            plan_before=str(old_plan), plan_after=str(new_plan)))
        return True

    # -- convenience ------------------------------------------------------
    def run(self, batches) -> List[dict]:
        return [self.step(xb, yb) for xb, yb in batches]


# -- DP device-drop recovery ----------------------------------------------
def recover_segment_partials(inner, params, xb, yb, partials, *,
                             grad_segments: int, lost,
                             reduce_schedule: str = "sequential"):
    """Recompute lost segment partials and recombine canonically.

    ``partials`` is a per-parameter stack of per-segment gradient codes
    (leading segment axis, as ``per_segment_grads`` emits) in which the
    slots named by ``lost`` are unavailable — a dropped device, a lost
    all-gather message (their current contents are ignored).  Because the
    canonical segmentation makes slot ``s`` a pure function of segment
    ``s``'s batch rows, each lost slot is recomputed from exactly those
    rows (``per_segment_grads(rows_s, 1)``), spliced in, and the full
    stack folded on the fixed schedule — so the combined gradients (and
    any update applied to them) are **bit-identical** to a fresh run at
    the surviving device count: device count never changed which
    arithmetic combines a segment, only where it was computed.

    Returns ``{param: combined grad}`` (pass to ``apply_updates``).
    """
    from ..distributed.lns_reduce import combine_partials
    b = xb.shape[0]
    if b % grad_segments:
        raise ValueError(
            f"batch {b} not divisible into {grad_segments} segments")
    seg = b // grad_segments
    lost = sorted(set(int(s) for s in lost))
    for s in lost:
        if not (0 <= s < grad_segments):
            raise ValueError(
                f"lost segment {s} out of range [0, {grad_segments})")
    repaired = {k: g for k, g in partials.items()}
    for s in lost:
        sl = slice(s * seg, (s + 1) * seg)
        g1, _ = inner.per_segment_grads(params, xb[sl], yb[sl], 1)
        for k in repaired:
            g = repaired[k]
            code = g.code.at[s].set(g1[k].code[0])
            sign = g.sign.at[s].set(g1[k].sign[0])
            repaired[k] = type(g)(code, sign)
    return {k: combine_partials(g, inner.param_engines[k],
                                schedule=reduce_schedule)
            for k, g in repaired.items()}


def shrink(model, surviving: int):
    """Rebuild a DP model on ``surviving`` devices (post device drop).

    The canonical segmentation is fixed by the plan's
    ``reduce.grad_segments``, so the shrunk model trains bit-identically
    to the pre-drop model (``surviving`` must divide ``grad_segments``).
    """
    import dataclasses as _dc

    from ..distributed.lns_dp import LNSDataParallelMLP
    if not isinstance(model, LNSDataParallelMLP):
        raise TypeError("shrink() applies to LNSDataParallelMLP models")
    dp = _dc.replace(model.dp, num_devices=surviving)
    return LNSDataParallelMLP(model.cfg, dp)
