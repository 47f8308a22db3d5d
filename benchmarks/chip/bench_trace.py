"""Reduction of a profiler trace to device busy time, idle share and a
breakdown.

The trace is the ``.xplane.pb`` that ``jax.profiler.trace`` writes.  A run
marks its measured window with a host annotation named :data:`WINDOW`; the
device's work is the events of the ``XLA Ops`` line of each ``/device:``
plane, and each op is named by the ``XLA Modules`` event (the program) it
runs in.  Busy time is the length of the union of the op intervals inside
the window, so overlapping ops count once; idle gaps are the window's
stretches outside that union, each named by the innermost host event that
spans the gap's midpoint.

The arithmetic takes plain ``(start_ns, end_ns)`` lists, so it is checked on
small synthetic traces without a profiler.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

#: host annotation that brackets the measured window
WINDOW = "bench.window"
DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
TOP = 10


def merge(intervals):
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(intervals, lo, hi) -> float:
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    return float(sum(e - s for s, e in merge(clip(intervals, lo, hi))))


def gaps(intervals, lo, hi):
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in merge(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


@dataclass
class DeviceTrace:
    """What the reduction reads from one traced window."""
    window_ns: tuple[float, float]
    #: per device plane: list of (start_ns, end_ns, name)
    ops: dict[str, list] = field(default_factory=dict)
    #: host events: list of (start_ns, end_ns, name)
    host: list = field(default_factory=list)

    @property
    def window_s(self) -> float:
        lo, hi = self.window_ns
        return (hi - lo) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices traced."""
        lo, hi = self.window_ns
        if not self.ops:
            return 0.0
        per = [busy_ns([(s, e) for s, e, _ in ops], lo, hi)
               for ops in self.ops.values()]
        return sum(per) / len(per) * 1e-9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def top_ops(self, n: int = TOP):
        """``[[name, seconds], ...]``: the op names that took most device
        time in the window, summed over devices and divided by their count."""
        lo, hi = self.window_ns
        tot: dict[str, float] = {}
        for ops in self.ops.values():
            for s, e, name in ops:
                s, e = max(s, lo), min(e, hi)
                if e > s:
                    tot[name] = tot.get(name, 0.0) + (e - s)
        k = max(1, len(self.ops))
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9 / k] for name, ns in ranked]

    def idle_gaps(self, n: int = TOP):
        """``[[host activity, seconds], ...]``: the longest idle stretches
        of the first device, each named by what the host was doing."""
        if not self.ops:
            return []
        lo, hi = self.window_ns
        first = sorted(self.ops)[0]
        found = gaps([(s, e) for s, e, _ in self.ops[first]], lo, hi)
        found.sort(key=lambda g: g[0] - g[1])
        return [[self.host_activity((s + e) / 2), (e - s) * 1e-9]
                for s, e in found[:n]]

    def host_activity(self, t: float) -> str:
        best = None
        for s, e, name in self.host:
            if s <= t <= e and name != WINDOW and \
                    (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else "host idle"


def _module_of(ops, modules):
    """Name each op ``module:op`` by the module event that contains it."""
    modules = sorted(modules)
    out, j = [], 0
    for s, e, name in sorted(ops):
        while j < len(modules) and modules[j][1] < s:
            j += 1
        mod = modules[j][2] if j < len(modules) and modules[j][0] <= s \
            else "?"
        out.append((s, e, f"{mod}:{name}"))
    return out


def read_xplane(log_dir: str) -> DeviceTrace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    window = None
    host, ops = [], {}
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns
                    e = s + ev.duration_ns
                    if ev.name == WINDOW:
                        window = (s, e)
                    if ev.duration_ns > 0:
                        host.append((s, e, ev.name))
        elif plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                continue
            # an op's name is its HLO text; keep the instruction name
            dev_ops = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                        ev.name.split(" = ", 1)[0])
                       for ev in lines[OPS_LINE].events]
            mods = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in lines[MODULES_LINE].events] \
                if MODULES_LINE in lines else []
            ops[plane.name] = _module_of(dev_ops, mods)
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    return DeviceTrace(window_ns=window, ops=ops, host=host)
