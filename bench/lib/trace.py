"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
per-operation time and the longest idle gaps.

The device's operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<i>`` plane, and its programs those of the ``XLA Modules``
line.  Off the TPU (the tests) the operations are the events of the CPU
client's threads on the host plane.  The host's activity is the
``jax.profiler.TraceAnnotation`` spans the harness puts around each call
into the program; the one named ``window`` marks the measured window, and
everything is clipped to it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[int, int]                 # [start, end) in nanoseconds
Event = Tuple[str, int, int]               # (name, start, end)

WINDOW = "window"
# the harness's own host annotations; a gap is labelled by the one it
# overlaps most, "host idle" by none
ANNOTATIONS = ("fit", "submit", "wait", "datagen")


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """The union of ``intervals`` as sorted, disjoint intervals."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle intervals of ``[lo, hi)`` between the disjoint ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Event]
    modules: List[Event]
    busy: List[Interval]          # union of the ops, clipped to the window

    @property
    def busy_ns(self) -> int:
        return total(self.busy)

    def op_ns(self, match) -> int:
        """Device time of the ops whose name satisfies ``match``, counting
        overlapping ops once."""
        return total(union([(s, e) for n, s, e in self.ops if match(n)]))

    def module_ns(self, match) -> int:
        return total(union([(s, e) for n, s, e in self.modules
                            if match(n)]))


@dataclasses.dataclass
class Reduction:
    window: Interval
    devices: List[Device]
    host: List[Event]             # the harness's annotations

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    def fullest(self) -> Device:
        return max(self.devices, key=lambda d: d.busy_ns)

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9

    def idle_share(self) -> float:
        """1 - busy / window on the fullest device."""
        return 1.0 - self.fullest().busy_ns / self.window_ns

    def top_ops(self, n: int = 10) -> List[list]:
        """The ``n`` operations with the most device time, in seconds
        averaged over the devices."""
        tot: Dict[str, int] = {}
        for d in self.devices:
            for name, s, e in d.ops:
                tot[name] = tot.get(name, 0) + e - s
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / len(self.devices) / 1e9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The ``n`` longest idle gaps of the fullest device, each labelled
        by the host annotation that overlaps it most."""
        lo, hi = self.window
        longest = sorted(gaps(self.fullest().busy, lo, hi),
                         key=lambda g: g[0] - g[1])[:n]
        out = []
        for s, e in longest:
            best, label = 0, "host idle"
            for name, hs, he in self.host:
                ov = min(e, he) - max(s, hs)
                if ov > best:
                    best, label = ov, name
            out.append([label, (e - s) / 1e9])
        return out


def short_name(name: str) -> str:
    """An operation's HLO instruction name: the TPU trace names each event
    by its whole instruction, ``%fusion.3 = f32[...] fusion(...)``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line, lo: int, hi: int) -> List[Event]:
    out = []
    for ev in line.events:
        s = int(ev.start_ns)
        e = s + int(ev.duration_ns)
        if e > lo and s < hi and e > s:
            out.append((short_name(ev.name), max(s, lo), min(e, hi)))
    return out


def reduce_profile(pd) -> Reduction:
    """Reduce a ``jax.profiler.ProfileData``."""
    host_plane, tpus = None, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            tpus.append(plane)
        elif plane.name == "/host:CPU":
            host_plane = plane
    if host_plane is None:
        raise ValueError("the trace has no host plane")
    host_lines = list(host_plane.lines)
    marks = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
             for line in host_lines for ev in line.events
             if ev.name == WINDOW]
    if not marks:
        raise ValueError(f"the trace has no {WINDOW!r} annotation")
    lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    host = [ev for line in host_lines for ev in _events(line, lo, hi)
            if ev[0] in ANNOTATIONS]
    devices = []
    for plane in sorted(tpus, key=lambda p: int(p.name.rsplit(":", 1)[1])):
        lines = {line.name: line for line in plane.lines}
        ops = _events(lines["XLA Ops"], lo, hi) if "XLA Ops" in lines else []
        mods = (_events(lines["XLA Modules"], lo, hi)
                if "XLA Modules" in lines else [])
        devices.append(Device(plane.name, ops, mods,
                              union([(s, e) for _, s, e in ops])))
    if not devices:
        # no TPU: the CPU client's threads run the device's work
        ops = [ev for line in host_lines if line.name.startswith("tf_XLA")
               for ev in _events(line, lo, hi)]
        devices.append(Device("/host:CPU", ops, [],
                              union([(s, e) for _, s, e in ops])))
    return Reduction((lo, hi), devices, host)


def load(directory: str) -> Reduction:
    """Reduce the newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return reduce_profile(ProfileData.from_file(max(paths,
                                                    key=os.path.getmtime)))

