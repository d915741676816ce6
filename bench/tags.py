"""What the program names in its own trace, read back: the tags on its
kernel launches and its host spans.

Every Pallas launch of the program passes ``metadata={"kind": ...}`` to
``pl.pallas_call``; it lands in the custom call's
``frontend_attributes={kernel_metadata={...}}``, which is part of the
launch's event name on the device's ``XLA Ops`` line, so ``trace.Trace``
keeps it.  The six ⊞-MAC kinds add the launch's output rows ``r``,
columns ``c`` and contraction depth ``ct`` as given, and ``rp``/``cp``/
``ctp`` as padded for the grid.  A launch the program does not tag
carries ``kernel_metadata={}``, and every reader here then returns None.

The program's host spans (``repro.*``, e.g. ``repro.train_step`` around
each call of a train-step entry point) lie on the ``/host:CPU`` plane;
:func:`program_spans` reads them from a profile.  ``trace.Trace`` keeps
only the harness's ``bench.*`` spans, so the readers of program spans
look for them as ``trace.program_spans`` and return None where the trace
has none.
"""
from __future__ import annotations

import json
import re

import trace as tr

METADATA = re.compile(r"kernel_metadata=(\{[^{}]*\})")
#: The ⊞-MAC launches, and how they split into the step's three products.
FWD = ("fwd", "fused_fwd")
DX = ("dx",)
DW = ("dw", "dw_update", "dw_partials")
MAC_KINDS = FWD + DX + DW
#: The host span around each call of a train-step entry point.
TRAIN_STEP = "repro.train_step"


def metadata(text: str) -> dict:
    """A kernel launch's ``kernel_metadata``, or {} for any other
    operation and for an untagged launch.  (XLA copies the attribute
    onto the get-tuple-elements that unpack a launch's outputs; only the
    custom call itself counts.)"""
    if "tpu_custom_call" not in text:
        return {}
    m = METADATA.search(text)
    if not m:
        return {}
    try:
        return json.loads(m.group(1))
    except ValueError:
        return {}


class Kind:
    """Matches the launches tagged with one of ``kinds``; usable wherever
    ``trace.Trace`` takes a pattern (``matched_s``, ``count``)."""

    def __init__(self, *kinds):
        self.kinds = frozenset(kinds)

    def search(self, text: str) -> bool:
        return metadata(text).get("kind") in self.kinds


def kind_ms_per_step(ctx, kinds):
    """Device time of the launches of these kinds per step, ms (per chip)."""
    k = Kind(*kinds)
    if not ctx.trace.count(k):
        return None
    return 1e3 * ctx.trace.matched_s(k) / ctx.steps


def mac_fill(ctx):
    """Useful ⊞-MACs over the ⊞-MACs the grids ran, over the ⊞-MAC
    launches in the window, %: Σ r·c·ct / Σ rp·cp·ctp."""
    t = ctx.trace
    useful = launched = 0
    for evs in t.devices.values():
        for e in evs:
            if e.end <= t.lo or e.start >= t.hi:
                continue
            md = metadata(e.text)
            if md.get("kind") not in MAC_KINDS:
                continue
            useful += int(md["r"]) * int(md["c"]) * int(md["ct"])
            launched += int(md["rp"]) * int(md["cp"]) * int(md["ctp"])
    if not launched:
        return None
    return 100.0 * useful / launched


def program_spans(pd) -> list:
    """The program's host spans (names starting ``repro.``) in a
    ``jax.profiler.ProfileData``, sorted by start."""
    out = [tr.Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                    ev.name)
           for plane in pd.planes if plane.name.startswith("/host")
           for line in plane.lines for ev in line.events
           if ev.name.startswith("repro.")]
    return sorted(out, key=lambda e: e.start)


def _step_intervals(t):
    """The ``repro.train_step`` spans clipped to the window, merged."""
    out = []
    for e in sorted(getattr(t, "program_spans", None) or (),
                    key=lambda e: e.start):
        if e.name != TRAIN_STEP:
            continue
        s, u = max(e.start, t.lo), min(e.end, t.hi)
        if u <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], u)
        else:
            out.append([s, u])
    return out


def host_ms_per_step(ctx):
    """Host time inside ``repro.train_step`` per step, ms."""
    iv = _step_intervals(ctx.trace)
    if not iv:
        return None
    return 1e3 * sum(u - s for s, u in iv) * 1e-9 / ctx.steps


def _overlap(a, b):
    """Total length of the intersection of two sorted interval lists."""
    tot, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def step_idle_share(ctx):
    """The share of the devices' idle time in the window that falls inside
    a ``repro.train_step`` span, %."""
    t = ctx.trace
    iv = _step_intervals(t)
    if not iv or not t.devices:
        return None
    idle = inside = 0
    for d in t.devices:
        gaps = t.gaps(d)
        idle += sum(u - s for s, u in gaps)
        inside += _overlap(gaps, iv)
    if not idle:
        return None
    return 100.0 * inside / idle
