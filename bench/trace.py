"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: the device's busy intervals over the traced window, the device
operations matched by name, and a breakdown of the largest operations and
the longest idle gaps.

The window is bounded by the harness's own host spans (``bench.*``,
written with ``jax.profiler.TraceAnnotation`` around each call into the
program).  A device is a plane named ``/device:TPU:<n>``; its operations
are the events of its ``XLA Ops`` line.
"""
from __future__ import annotations

import glob
import os
import re

#: The ⊞-MAC kernels.  Their ``pallas_call``s carry no name, so a launch
#: shows in the trace as the custom call of the jitted wrapper that makes
#: it (``kernels/lns_matmul/ops.py``): ``_call`` (forward, dX, dW),
#: ``_call_fused_fwd``, ``_call_dw_update`` and ``_call_dw_partials``; the
#: event's name is the HLO instruction, ``%_call.300 = (...)
#: custom-call(...), custom_call_target="tpu_custom_call"``.  The fold of
#: the data-parallel combine (``kernels/lns_boxsum``) is launched from a
#: wrapper named ``_call`` too; its launch is tagged ``"kind":"boxsum"``
#: and left out.
MAC = re.compile(r"^%_call(_fused_fwd|_dw_update|_dw_partials)?\.\d+ = "
                 r'(?![\s\S]*"kind":"boxsum").*tpu_custom_call')
#: Collectives between chips, by their HLO opcodes.
COLLECTIVE = re.compile(r"^%(all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all)[-.\w]* = ")
#: Ops that contain others (a loop over layers): their time is their
#: children's, so the breakdown leaves them out.
CONTAINER = re.compile(r"^%(while|conditional|call)\.\d+ = ")


class Event:
    __slots__ = ("name", "start", "end", "text")

    def __init__(self, name, start, end, text):
        self.name, self.start, self.end, self.text = name, start, end, text

    @property
    def dur(self):
        return self.end - self.start


def _text(ev) -> str:
    """The event's name and its string stats, for matching by name."""
    parts = [ev.name]
    for _, v in ev.stats:
        if isinstance(v, str):
            parts.append(v)
    return " ".join(parts)


class Trace:
    """One traced window: host spans, and per device its operations."""

    def __init__(self, spans, devices):
        self.spans = spans              # [Event] host spans bench.*
        self.devices = devices          # {plane name: [Event]} sorted
        if not spans:
            raise ValueError("the trace holds no bench.* host span")
        self.lo = min(e.start for e in spans)
        self.hi = max(e.end for e in spans)

    @classmethod
    def from_profile(cls, pd) -> "Trace":
        spans, devices = [], {}
        for plane in pd.planes:
            if plane.name.startswith("/host"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("bench."):
                            spans.append(Event(ev.name, ev.start_ns,
                                               ev.start_ns + ev.duration_ns,
                                               ev.name))
            elif re.fullmatch(r"/device:TPU:\d+", plane.name):
                evs = [Event(ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns, _text(ev))
                       for line in plane.lines if line.name == "XLA Ops"
                       for ev in line.events]
                devices[plane.name] = sorted(evs, key=lambda e: e.start)
        return cls(spans, devices)

    @classmethod
    def from_dir(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise ValueError(f"expected one .xplane.pb under {path}, found "
                             f"{len(files)}")
        return cls.from_profile(ProfileData.from_file(files[0]))

    # -- windows and intervals ------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def _clip(self, evs):
        for e in evs:
            s, t = max(e.start, self.lo), min(e.end, self.hi)
            if t > s:
                yield s, t

    def busy_intervals(self, dev: str):
        """The union of the device's operation intervals in the window."""
        out = []
        for s, t in sorted(self._clip(self.devices[dev])):
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], t)
            else:
                out.append([s, t])
        return out

    def busy_s(self) -> float:
        """Seconds some operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = sum(t - s for d in self.devices
                  for s, t in self.busy_intervals(d))
        return tot * 1e-9 / len(self.devices)

    def gaps(self, dev: str):
        """Idle intervals of the device within the window."""
        out, cur = [], self.lo
        for s, t in self.busy_intervals(dev):
            if s > cur:
                out.append((cur, s))
            cur = max(cur, t)
        if self.hi > cur:
            out.append((cur, self.hi))
        return out

    def matched_s(self, pattern) -> float:
        """Seconds of the operations whose name or stats match, summed
        over the devices and averaged over them (overlaps counted once
        per device)."""
        if not self.devices:
            return 0.0
        tot = 0.0
        for evs in self.devices.values():
            hit = [e for e in evs if pattern.search(e.text)]
            merged = []
            for s, t in sorted(self._clip(hit)):
                if merged and s <= merged[-1][1]:
                    merged[-1][1] = max(merged[-1][1], t)
                else:
                    merged.append([s, t])
            tot += sum(t - s for s, t in merged)
        return tot * 1e-9 / len(self.devices)

    def count(self, pattern) -> int:
        return sum(1 for evs in self.devices.values() for e in evs
                   if pattern.search(e.text) and e.end > self.lo
                   and e.start < self.hi)

    # -- breakdown ------------------------------------------------------
    def _span_at(self, s, t) -> str:
        """The harness span that covers most of [s, t); of equal cover,
        the shortest, which is the innermost."""
        best = max(self.spans, key=lambda e: (min(t, e.end) - max(s, e.start),
                                              -e.dur))
        return best.name if min(t, best.end) > max(s, best.start) else "none"

    def breakdown(self, top: int = 10) -> dict:
        """The operations that took most device time (summed over the
        devices, divided by their number) and the longest idle gaps, each
        named by the harness span that was open during it."""
        n = max(1, len(self.devices))
        tot: dict = {}
        for evs in self.devices.values():
            for s, t, e in ((max(e.start, self.lo), min(e.end, self.hi), e)
                            for e in evs):
                if t > s and not CONTAINER.search(e.name):
                    k = e.name[:120]
                    tot[k] = tot.get(k, 0) + (t - s)
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(((t - s, s, t) for d in self.devices
                       for s, t in self.gaps(d)), reverse=True)[:top]
        return {"device_ops": [[k, v * 1e-9 / n] for k, v in ops],
                "idle_gaps": [[self._span_at(s, t), g * 1e-9]
                              for g, s, t in gaps]}

