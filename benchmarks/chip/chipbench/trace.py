"""The reduction from a profiler trace to the numbers the metrics read.

``jax.profiler`` writes one ``.xplane.pb`` per traced run.  Its device
planes (``/device:TPU:<n>``) hold a line of XLA operations with their start
and duration; its host plane holds the benchmark's spans, written there as
``TraceAnnotation`` events, on the same clock.  From those this module
takes:

* the traced window: the host span named ``window``;
* busy time: the union of the operation intervals inside the window,
  averaged over the chips that ran any;
* kernel time and calls by name: operations whose own name starts with
  a given kernel name (a Pallas kernel appears as a custom call named
  after the jitted function that holds its ``pallas_call``, such as
  ``lif_update_pallas.3``), in the window or in the whole trace;
* the breakdown: the operations that took the most time of their own
  (a ``while`` or ``call`` op less the ops nested in it), and the longest
  idle gaps, each named after the innermost benchmark span the host was
  in.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: Device planes of TPU chips (not their non-core helpers).
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: The line of a device plane that holds one event per XLA operation.
OPS_LINE = "XLA Ops"
#: At most this many entries in each list of the breakdown.
TOP = 10


@dataclasses.dataclass
class Op:
    name: str          # an operation's own HLO name, e.g. "fusion.6"
    t0: float          # seconds on the trace clock
    t1: float


def op_name(event_name: str) -> str:
    """The operation's own name: a TPU trace names each op event by its
    whole HLO instruction (``%fusion.6 = f32[...] fusion(...)``), whose
    operands would otherwise match other names."""
    return event_name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Reduced:
    """What one traced window holds."""

    window: Tuple[float, float]                 # trace clock, seconds
    ops: Dict[str, List[Op]]                    # device plane -> its ops
    host: List[Op]                              # benchmark spans, host side

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _busy_intervals(self, plane_ops: Sequence[Op]) -> List[Tuple[float, float]]:
        return union(clip(((o.t0, o.t1) for o in plane_ops), self.window))

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips used."""
        used = [ops for ops in self.ops.values() if ops]
        if not used:
            return 0.0
        return sum(sum(b - a for a, b in self._busy_intervals(ops))
                   for ops in used) / len(used)

    def in_window(self) -> Iterable[Op]:
        lo, hi = self.window
        for ops in self.ops.values():
            for o in ops:
                if o.t0 >= lo and o.t1 <= hi:
                    yield o

    def kernel(self, name: str, whole: bool = False) -> Tuple[float, int]:
        """(device seconds, calls) of the operations named for ``name``,
        summed over chips, inside the window, or anywhere in the trace
        with ``whole``."""
        ops = (o for v in self.ops.values() for o in v) if whole else self.in_window()
        seconds, calls = 0.0, 0
        for o in ops:
            if o.name == name or o.name.startswith(name + "."):
                seconds += o.t1 - o.t0
                calls += 1
        return seconds, calls

    def host_spans(self, name: str) -> List[Op]:
        """The benchmark's host spans named ``name`` inside the window, in
        the order they started."""
        lo, hi = self.window
        return sorted((o for o in self.host
                       if o.name == name and o.t0 >= lo and o.t1 <= hi),
                      key=lambda o: o.t0)

    def self_times(self) -> Dict[str, float]:
        """Seconds of each operation name less the ops nested inside it,
        summed over the window (ops on one line nest or are disjoint)."""
        out: Dict[str, float] = {}
        for ops in self.ops.values():
            lo, hi = self.window
            inside = sorted((o for o in ops if o.t0 >= lo and o.t1 <= hi),
                            key=lambda o: (o.t0, -o.t1))
            stack: List[List] = []          # [op, seconds of its children]
            for o in inside + [None]:
                while stack and (o is None or o.t0 >= stack[-1][0].t1):
                    top, child = stack.pop()
                    own = (top.t1 - top.t0) - child
                    out[top.name] = out.get(top.name, 0.0) + own
                    if stack:
                        stack[-1][1] += top.t1 - top.t0
                if o is not None:
                    stack.append([o, 0.0])
        return out

    def gaps(self) -> List[Tuple[float, float]]:
        """Idle intervals of the busiest chip inside the window."""
        used = [ops for ops in self.ops.values() if ops]
        if not used:
            return [self.window]
        busy = self._busy_intervals(max(used, key=len))
        out, t = [], self.window[0]
        for a, b in busy:
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if t < self.window[1]:
            out.append((t, self.window[1]))
        return out

    def host_activity(self, t0: float, t1: float) -> str:
        """The innermost benchmark span that covers most of [t0, t1]."""
        best, best_key = "untraced", (0.0, 0.0)
        for s in self.host:
            overlap = min(s.t1, t1) - max(s.t0, t0)
            if overlap <= 0:
                continue
            # more overlap first, then the shorter (innermost) span
            key = (round(overlap / (t1 - t0), 3), -(s.t1 - s.t0))
            if key > best_key:
                best, best_key = s.name, key
        return best

    def breakdown(self) -> dict:
        top_ops = sorted(self.self_times().items(), key=lambda kv: -kv[1])[:TOP]
        longest = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in top_ops],
                "idle_gaps": [[self.host_activity(a, b), b - a]
                              for a, b in longest]}


def clip(intervals: Iterable[Tuple[float, float]], window: Tuple[float, float]):
    lo, hi = window
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Disjoint, sorted union of half-open intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line, name=lambda n: n) -> Iterable[Op]:
    for e in line.events:
        yield Op(name(e.name), e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9)


def reduce(path: Path, window_span: str, known_spans: Optional[set] = None
           ) -> Reduced:
    """Reduce the trace at ``path`` (a directory or an ``.xplane.pb``)."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        path = find_xplane(path)
    data = ProfileData.from_file(str(path))
    ops: Dict[str, List[Op]] = {}
    host: List[Op] = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops[plane.name] = [o for line in plane.lines if line.name == OPS_LINE
                               for o in _events(line, op_name)]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(o for o in _events(line)
                            if known_spans is None or o.name in known_spans)
    windows = [o for o in host if o.name == window_span]
    if not windows:
        raise ValueError(f"trace {path} holds no host span {window_span!r}")
    w = max(windows, key=lambda o: o.t1 - o.t0)
    return Reduced(window=(w.t0, w.t1), ops=ops,
                   host=[o for o in host if o is not w])
