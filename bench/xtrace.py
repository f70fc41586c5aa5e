"""From a ``jax.profiler`` trace (``.xplane.pb``) to device busy time,
kernel time and idle gaps labelled by what the host was doing.

The device's operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<i>`` plane; host spans are the ``TraceAnnotation`` events
of the host plane (the program's ``repro.obs`` spans with
``profiler_bridge`` on, and the benchmark's own ``bench.window``).  All
times share the trace's clock, in nanoseconds.

The device's clock runs ahead of the host's in these traces (a TPU v5e
showed its programs start some 1.5 ms before the host launched them).
Where the host's launches (``TpuLoadedExecutable::ExecuteLaunch``) and
the device's programs (its ``XLA Modules`` line) pair up one to one, the
device's events are moved later by the least shift that puts every
program at or after its launch.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

#: host spans that label an idle gap, from the outermost to the innermost
SPAN_PREFIXES = ("bench.", "arena.", "engine.", "trainer.", "service.",
                 "store.")
#: the host event that launches one program on one device
LAUNCH = "TpuLoadedExecutable::ExecuteLaunch"
#: the eq.-(4) kernel's events: the Pallas custom call (the only Pallas
#: kernel on these paths), e.g. ``%fl_aggregate.1 = f32[589824]{...}
#: custom-call(...), custom_call_target="tpu_custom_call"``
KERNEL_PATTERN = r'custom-call\(.*custom_call_target="tpu_custom_call"'


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge ``[start, end)`` intervals into disjoint sorted ones."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals: Sequence[Interval]) -> float:
    return float(sum(e - s for s, e in intervals))


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi)`` that no busy interval covers."""
    out, t = [], lo
    for s, e in clip(busy, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head.lstrip("%")


@dataclasses.dataclass
class Trace:
    """One trace, reduced to what the per-layer readers need."""
    devices: Dict[str, List[Tuple[float, float, str]]]   # plane -> ops
    spans: List[Tuple[float, float, str]]                # host spans

    @classmethod
    def load(cls, path: str) -> "Trace":
        import jax
        prof = jax.profiler.ProfileData.from_file(path)
        devices, modules, spans, launches = {}, {}, [], []
        for plane in prof.planes:
            if re.fullmatch(r"/device:TPU:\d+", plane.name):
                ops, mods = [], []
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops.extend((e.start_ns, e.start_ns + e.duration_ns,
                                    e.name) for e in line.events)
                    elif line.name == "XLA Modules":
                        mods.extend(e.start_ns for e in line.events)
                devices[plane.name], modules[plane.name] = ops, sorted(mods)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIXES):
                            spans.append((e.start_ns,
                                          e.start_ns + e.duration_ns, e.name))
                        elif e.name == LAUNCH:
                            launches.append(e.start_ns)
        launches.sort()
        for name, mods in modules.items():
            if mods and len(mods) == len(launches):
                shift = max(0.0, max(h - d for h, d in zip(launches, mods)))
                devices[name] = [(s + shift, e + shift, n)
                                 for s, e, n in devices[name]]
        return cls(devices, sorted(spans))

    # -- the window ---------------------------------------------------------

    def window(self, name: str = "bench.window") -> Interval:
        """The traced window: the benchmark's own span around the work."""
        marks = [(s, e) for s, e, n in self.spans if n == name]
        if not marks:
            raise ValueError(f"no {name!r} span in the trace")
        return marks[0]

    def busy(self, device: str) -> List[Interval]:
        return union([(s, e) for s, e, _ in self.devices[device]])

    def busy_s(self, lo: float, hi: float) -> float:
        """Busy seconds in ``[lo, hi)``, averaged over the devices."""
        if not self.devices:
            return 0.0
        total = sum(length(clip(self.busy(d), lo, hi)) for d in self.devices)
        return total / len(self.devices) * 1e-9

    # -- kernels and operations ---------------------------------------------

    def events_matching(self, pattern: str, lo: float, hi: float
                        ) -> List[Tuple[float, float, str]]:
        rx = re.compile(pattern)
        return [(s, e, n) for d in self.devices.values() for s, e, n in d
                if lo <= s < hi and rx.search(n)]

    def top_ops(self, lo: float, hi: float, count: int = 10
                ) -> List[Tuple[str, float]]:
        """The operations that took most device seconds (summed over
        devices and calls)."""
        tot: Dict[str, float] = defaultdict(float)
        for d in self.devices.values():
            for s, e, n in d:
                if lo <= s < hi:
                    tot[op_name(n)] += (e - s) * 1e-9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:count]

    # -- idle time and the host spans around it -----------------------------

    def label(self, t: float) -> str:
        """The innermost host span open at ``t`` (``idle`` if none)."""
        best: Optional[Tuple[float, float, str]] = None
        for s, e, n in self.spans:
            if s > t:
                break
            if e > t and (best is None or s >= best[0]):
                best = (s, e, n)
        return best[2] if best else "idle"

    def idle_gaps(self, lo: float, hi: float, count: int = 10
                  ) -> List[Tuple[str, float]]:
        """The longest idle gaps of the first device, each labelled by
        the innermost host span open at its midpoint."""
        if not self.devices:
            return []
        dev = sorted(self.devices)[0]
        g = sorted(gaps(self.busy(dev), lo, hi), key=lambda i: i[0] - i[1])
        return [(self.label(0.5 * (s + e)), (e - s) * 1e-9)
                for s, e in g[:count]]

    def idle_inside(self, span_name: str, lo: float, hi: float
                    ) -> Tuple[float, int]:
        """(idle seconds of the first device inside spans named
        ``span_name`` within the window, number of such spans); (0, 0)
        where the trace holds no device."""
        if not self.devices:
            return 0.0, 0
        dev = sorted(self.devices)[0]
        busy = self.busy(dev)
        inside = [(s, e) for s, e, n in self.spans
                  if n == span_name and s >= lo and e <= hi]
        idle = sum(length(gaps(busy, s, e)) for s, e in inside)
        return idle * 1e-9, len(inside)
