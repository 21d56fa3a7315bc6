"""What the program's own spans and scopes add to a profiler trace.

``xplane.Trace`` reduces a trace to the device's busy time and the harness's
``bench.*`` spans; :class:`ProgramTrace` reads the same file once more for
what the program writes itself (``repro.runtime.spans`` and the named
scopes of the decide program's stages):

- ``program_spans``: the ``r2e.*`` host spans in the window;
- ``runtime_in(span, pattern)``: host seconds of the runtime's own events
  (PJRT ``Execute``, buffer allocation, transfers) nested in a program span;
- ``scope_time_s(scope)``: device seconds of the operations whose HLO
  ``op_name`` lies under a named scope (``r2e.repair``);
- ``spans``: the harness's and the program's, so that ``idle_gaps``
  labels a gap by the innermost span of either kind.

An operation's ``op_name`` comes from the compiled program's HLO text (a
v5e trace's operation events carry no op_name stat); an operation not found
there counts as unscoped.  A pattern or scope that matches nothing reads
``None``, never 0.
"""
from __future__ import annotations

import bisect
import re

import xplane
from xplane import Event, covered, union

PROGRAM_PREFIX = "r2e."
SCOPES = ("r2e.gate", "r2e.stage1", "r2e.ccg", "r2e.consistency",
          "r2e.repair")
#: the runtime's events inside a launch, each counted once, in this order
LAUNCH_PARTS = (("alloc", r"Allocat"),
                ("transfer", r"Linearize|TransferToDevice|H2D"),
                ("execute", r"Executable"))


def hlo_op_names(text: str) -> dict:
    """{instruction: op_name} of every instruction of an HLO module's text
    that carries an op_name (names without the "%", as a CPU trace gives
    them)."""
    rx = re.compile(r'^\s*(?:ROOT )?%(\S+) = .*?op_name="([^"]*)"', re.M)
    return dict(rx.findall(text))


def intersect(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        out += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


class ProgramTrace(xplane.Trace):
    def __init__(self, path: str, *, hlo_text: str | None = None, **planes):
        from jax.profiler import ProfileData

        super().__init__(path, **planes)
        host = planes.get("host_plane", xplane.HOST_PLANE)
        self.host_lines: list[list[Event]] = []
        self.host_line_names: list[str] = []
        for plane in ProfileData.from_file(path).planes:
            if re.search(host, plane.name):
                for line in plane.lines:
                    self.host_line_names.append(line.name)
                    evs = [Event(e.name, e.start_ns, e.end_ns)
                           for e in line.events if e.duration_ns > 0]
                    self.host_lines.append(sorted(
                        xplane.clip(evs, self.lo, self.hi),
                        key=lambda ev: ev.start))
        self.op_names = hlo_op_names(hlo_text or "")
        self._scope_memo: dict[str, frozenset] = {}
        self._starts = [[ev.start for ev in line] for line in self.host_lines]
        self.program_spans = [ev for line in self.host_lines for ev in line
                              if ev.name.startswith(PROGRAM_PREFIX)]
        # every span, the harness's and the program's: span_count, span_s
        # and idle_gaps (labelled by the innermost) read both
        self.spans = self.spans + self.program_spans

    # -- host ---------------------------------------------------------------
    def _inside(self, sp: Event):
        """(thread line, event) of the host events nested in ``sp``, on any
        line: the profiler writes one thread's Python-side events (the
        spans, ``PjitFunction``, ``DevicePut``) and its runtime events
        (``…Execute``, ``…Allocate``) on two lines (``python3`` and
        ``main/<tid>`` on the chip's host)."""
        for name, line, starts in zip(self.host_line_names, self.host_lines,
                                      self._starts):
            i = bisect.bisect_left(starts, sp.start)
            j = bisect.bisect_right(starts, sp.end)
            yield from ((name, ev) for ev in line[i:j]
                        if ev is not sp and ev.end <= sp.end)

    def _nested(self, span_name: str, pattern: str):
        """Per span named ``span_name``: the merged intervals of the host
        events nested in it whose name matches ``pattern``."""
        rx = re.compile(pattern)
        for sp in self.spans:
            if sp.name == span_name:
                yield union((ev.start, ev.end) for _, ev in self._inside(sp)
                            if rx.search(ev.name))

    def runtime_in(self, span_name: str, pattern: str) -> float | None:
        """Host seconds of the events matching ``pattern`` nested in the
        spans named ``span_name``."""
        got = [covered(ivs) for ivs in self._nested(span_name, pattern)
               if ivs]
        return sum(got) * 1e-9 if got else None

    def launch_split(self, span_name: str = "r2e.launch") -> dict | None:
        """Host seconds inside the spans named ``span_name``, divided into
        the runtime's parts (``LAUNCH_PARTS``, each interval counted by the
        first part that matches it) and the uncovered rest ("python")."""
        total = sum(sp.end - sp.start for sp in self.spans
                    if sp.name == span_name)
        if not total:
            return None
        out, seen = {}, 0.0
        pattern = ""
        for part, rx in LAUNCH_PARTS:
            pattern = f"{pattern}|{rx}" if pattern else rx
            got = sum(covered(ivs) for ivs in
                      self._nested(span_name, pattern))
            out[part] = (got - seen) * 1e-9
            seen = got
        out["python"] = (total - seen) * 1e-9
        return out

    def host_events_in(self, span_name: str, n: int = 20):
        """[[thread line, event, seconds]] of the n host events nested in
        the spans named ``span_name`` with the most time (the union of each
        line's events of one name)."""
        ivs: dict[tuple, list] = {}
        for sp in self.spans:
            if sp.name == span_name:
                for line, ev in self._inside(sp):
                    ivs.setdefault((line, ev.name), []).append(
                        (ev.start, ev.end))
        top = sorted(((k, covered(v)) for k, v in ivs.items()),
                     key=lambda kv: -kv[1])[:n]
        return [[line, ev, t * 1e-9] for (line, ev), t in top]

    # -- device -------------------------------------------------------------
    def idle_under_s(self, span_name: str) -> float | None:
        """Seconds of the window in which no operation ran on the first
        device and the host was inside a span named ``span_name``."""
        if not self.ops:
            return None
        spans = union((s.start, s.end) for s in self.spans
                      if s.name == span_name)
        if not spans:
            return None
        busy = union((ev.start, ev.end) for ev in self.ops[0])
        return (covered(spans) - intersect(spans, busy)) * 1e-9

    def _scopes(self, name: str) -> frozenset:
        """The named scopes on an operation's op_name path (memoized)."""
        got = self._scope_memo.get(name)
        if got is None:
            on = self.op_names.get(xplane.op_name(name).lstrip("%"), "")
            got = frozenset(s for s in on.split("/")
                            if s.startswith(PROGRAM_PREFIX))
            self._scope_memo[name] = got
        return got

    def scope_time_s(self, scope: str | None) -> float | None:
        """Device seconds (busy union, averaged over the device planes) of
        the operations whose op_name lies under ``scope``; ``None`` for the
        operations under any of ``SCOPES``."""
        want = frozenset(SCOPES if scope is None else (scope,))
        hits = [[ev for ev in ops if self._scopes(ev.name) & want]
                for ops in self.ops]
        if not any(hits):
            return None
        per = [covered((ev.start, ev.end) for ev in evs) for evs in hits]
        return sum(per) / len(per) * 1e-9


def metrics(t: ProgramTrace) -> dict:
    """The per-layer readings the program's spans and scopes give, per
    traced round (``bench.round``); a reading with nothing to read is left
    out."""
    n = t.span_count("bench.round")
    got = {}
    if not n or t.window_s <= 0:
        return got
    launch = t.span_s("r2e.launch")
    alloc = t.runtime_in("r2e.launch", LAUNCH_PARTS[0][1])
    idle = t.idle_under_s("r2e.launch")
    repair = t.scope_time_s("r2e.repair")
    if launch is not None:
        got["host_us_per_round.launch"] = launch * 1e6 / n
    if alloc is not None:
        got["host_us_per_round.alloc"] = alloc * 1e6 / n
    if idle is not None:
        got["device_idle_pct.route.launch"] = 100.0 * idle / t.window_s
    if repair is not None:
        got["device_us_per_round.repair"] = repair * 1e6 / n
    return got


def report(t: ProgramTrace) -> str:
    """One line: device us per round under each scope, the scoped union's
    share of the busy time and the unscoped rest, and the host us per round
    inside ``r2e.launch`` by part."""
    n = max(t.span_count("bench.round"), 1)
    us = lambda s: "none" if s is None else f"{s * 1e6 / n:.1f}"
    busy = t.busy_s or 0.0
    scoped = t.scope_time_s(None) or 0.0
    parts = [f"{s} {us(t.scope_time_s(s))}" for s in SCOPES]
    line = (f"device us per round: {', '.join(parts)}; scoped "
            f"{us(scoped)} of busy {us(busy)} "
            f"({100.0 * scoped / busy if busy else 0.0:.2f}%), unscoped "
            f"{us(busy - scoped)}")
    split = t.launch_split()
    if split:
        line += ("; host us per round in r2e.launch: "
                 + ", ".join(f"{k} {us(v)}" for k, v in split.items()))
    return line
