"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: the device's busy union and idle share over the traced window, device
time per operation or program matched by a name pattern, the operations that
took most time, and the longest idle gaps labelled by the host span that
covers them.

Device events are the operation events of the device planes (one plane per
chip); host spans are the ``jax.profiler.TraceAnnotation`` spans the harness
writes around each round, route, dispatch and wait, named ``bench.*``.  The
traced window is the harness's ``bench.window`` span.  A pattern that
matches no event reads ``None``, never 0.
"""
from __future__ import annotations

import re
from dataclasses import dataclass

#: device planes and their lines on a TPU: one plane per chip; the leaf
#: operations on "XLA Ops", whole compiled programs on "XLA Modules"
TPU_PLANE = r"^/device:TPU:\d+$"
TPU_OPS = r"^XLA Ops$"
TPU_MODULES = r"^XLA Modules$"
HOST_PLANE = r"^/host:CPU$"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def op_name(name: str) -> str:
    """An operation event's name is its whole HLO instruction on a TPU
    ("%fusion.3 = f32[...] fusion(...)"): keep the instruction's name."""
    return name.split(" = ", 1)[0]


@dataclass(frozen=True)
class Event:
    name: str
    start: float        # ns
    end: float          # ns


def union(intervals):
    """Merged, sorted list of (start, end) covering the given intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def clip(events, lo, hi):
    return [Event(ev.name, max(ev.start, lo), min(ev.end, hi))
            for ev in events if ev.end > lo and ev.start < hi]


class Trace:
    """One traced window, reduced.

    ``device_plane`` / ``op_line`` / ``module_line`` are regular expressions
    on plane and line names; the defaults are a TPU's.  A test on a CPU
    trace points them at the CPU client's threads instead.
    """

    def __init__(self, path: str, *, device_plane: str = TPU_PLANE,
                 op_line: str = TPU_OPS, module_line: str = TPU_MODULES,
                 host_plane: str = HOST_PLANE):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        self.ops: list[list[Event]] = []          # per device plane
        self.modules: list[list[Event]] = []
        spans: list[Event] = []
        for plane in data.planes:
            if re.search(device_plane, plane.name):
                ops, mods = [], []
                for line in plane.lines:
                    if re.search(op_line, line.name):
                        ops += [Event(op_name(e.name), e.start_ns, e.end_ns)
                                for e in line.events if e.duration_ns > 0]
                    elif re.search(module_line, line.name):
                        mods += [Event(e.name, e.start_ns, e.end_ns)
                                 for e in line.events if e.duration_ns > 0]
                self.ops.append(ops)
                self.modules.append(mods)
            if re.search(host_plane, plane.name):
                for line in plane.lines:
                    spans += [Event(e.name, e.start_ns, e.end_ns)
                              for e in line.events
                              if e.name.startswith(SPAN_PREFIX)]
        windows = [s for s in spans if s.name == WINDOW_SPAN]
        if windows:
            lo = min(w.start for w in windows)
            hi = max(w.end for w in windows)
        else:
            every = [ev for ops in self.ops for ev in ops]
            lo = min((ev.start for ev in every), default=0.0)
            hi = max((ev.end for ev in every), default=0.0)
        self.lo, self.hi = lo, hi
        self.ops = [clip(ops, lo, hi) for ops in self.ops]
        self.modules = [clip(m, lo, hi) for m in self.modules]
        self.spans = [s for s in clip(spans, lo, hi) if s.name != WINDOW_SPAN]

    # -- whole window -------------------------------------------------------
    @property
    def n_devices(self) -> int:
        return len(self.ops)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float | None:
        """Seconds in which some operation ran on a device, averaged over
        the device planes; None when the trace holds no device operation."""
        per = [covered((ev.start, ev.end) for ev in ops) for ops in self.ops]
        if not any(per):
            return None
        return sum(per) / len(per) * 1e-9

    @property
    def idle_share(self) -> float | None:
        busy = self.busy_s
        if busy is None or self.window_s <= 0:
            return None
        return 1.0 - busy / self.window_s

    # -- by name ------------------------------------------------------------
    def _matching(self, pattern: str, modules: bool):
        rx = re.compile(pattern)
        src = self.modules if modules else self.ops
        return [[ev for ev in evs if rx.search(ev.name)] for evs in src]

    def time_s(self, pattern: str, modules: bool = False) -> float | None:
        """Device seconds of the operations (or programs) whose name matches
        ``pattern``, averaged over the device planes; None if none match."""
        hits = self._matching(pattern, modules)
        if not any(hits):
            return None
        per = [covered((ev.start, ev.end) for ev in evs) for evs in hits]
        return sum(per) / len(per) * 1e-9

    def count(self, pattern: str, modules: bool = False) -> int:
        """Executions matching ``pattern`` on the busiest device plane."""
        return max((len(h) for h in self._matching(pattern, modules)),
                   default=0)

    def span_count(self, name: str) -> int:
        return sum(1 for s in self.spans if s.name == name)

    def span_s(self, name: str) -> float | None:
        hits = [s.end - s.start for s in self.spans if s.name == name]
        return sum(hits) * 1e-9 if hits else None

    # -- breakdown ----------------------------------------------------------
    def top_ops(self, n: int = 10):
        """[[name, seconds]] of the n operations with the most device time
        (summed over their executions, averaged over the device planes)."""
        tot: dict[str, float] = {}
        for ops in self.ops:
            for ev in ops:
                tot[ev.name] = tot.get(ev.name, 0.0) + (ev.end - ev.start)
        k = max(self.n_devices, 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, t / k * 1e-9] for name, t in top]

    def idle_gaps(self, n: int = 10):
        """[[label, seconds]] of the n longest gaps in which no operation ran
        on the first device, each labelled by the innermost harness span
        that covers the gap's middle ("none" where no span does)."""
        if not self.ops:
            return []
        busy = union((ev.start, ev.end) for ev in self.ops[0])
        edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) / 2
            cover = [sp for sp in self.spans if sp.start <= mid <= sp.end]
            label = min(cover, key=lambda sp: sp.end - sp.start).name \
                if cover else "none"
            out.append([label, (e - s) * 1e-9])
        return out
