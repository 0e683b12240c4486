"""The program's launch phases in a traced run, on the profiler's clock.

The program marks each phase of a launch with a ``repro.tracing`` span:
``launch.prepare`` (forms, jit entry, inputs, operands), ``launch.carry``
(the scan's zero state, where one is built), ``launch.dispatch`` (the call
of the jitted entry) and ``launch.sync`` (each read of device arrays on
the host; attributes ``what``, ``arrays``, ``bytes``).  With
``jax.profiler.TraceAnnotation`` installed as the program's sink they
reach the trace's host plane, attributes included, beside the benchmark's
``sim.launch`` spans.  From there this module takes:

* per launch, the seconds of each phase nested in it and the arrays its
  host reads moved; over the launches, the share of their time the
  phases cover;
* the offset of the device clock against the host's: each
  ``launch.dispatch``, in order, pairs with the device module of its
  launch's jitted entry (``jit_checked``).  A module cannot start before
  its dispatch, so the least (module start - dispatch start) is the offset
  to within the shortest dispatch latency;
* the longest idle gaps of the device, each named after the phase the
  host was in, with device times read on the host's clock.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from . import trace

PHASES = ("launch.prepare", "launch.carry", "launch.dispatch", "launch.sync")
#: The device module of a launch's jitted entry: ``jit_<function>(<hash>)``.
ENTRY = "jit_checked"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    t0: float          # seconds on the trace clock
    t1: float
    attrs: Dict


def read(path: Path) -> Tuple[List[Event], List[trace.Op]]:
    """The program's phase spans on the host planes, in start order, and
    the modules of every device plane (a directory or an ``.xplane.pb``)."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        path = trace.find_xplane(path)
    data = ProfileData.from_file(str(path))
    events: List[Event] = []
    modules: List[trace.Op] = []
    for plane in data.planes:
        if trace._DEVICE_PLANE.match(plane.name):
            modules.extend(o for line in plane.lines
                           if line.name == MODULES_LINE
                           for o in trace._events(line))
        elif plane.name.startswith("/host:"):
            events.extend(
                Event(e.name, e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats))
                for line in plane.lines for e in line.events
                if e.name in PHASES)
    events.sort(key=lambda e: e.t0)
    modules.sort(key=lambda o: o.t0)
    return events, modules


def per_launch(launches: Sequence, events: Sequence[Event]) -> List[Dict]:
    """For each launch span (``t0``, ``t1``): its seconds, the seconds of
    each phase nested in it (summed where a phase recurs, as
    ``launch.sync`` does) and the arrays its ``launch.sync`` spans read."""
    rows = []
    for launch in launches:
        inside = [e for e in events if e.t0 >= launch.t0 and e.t1 <= launch.t1]
        seconds: Dict[str, float] = {}
        for e in inside:
            seconds[e.name] = seconds.get(e.name, 0.0) + (e.t1 - e.t0)
        rows.append({
            "launch_s": launch.t1 - launch.t0,
            "seconds": seconds,
            "arrays": sum(int(e.attrs.get("arrays", 0)) for e in inside
                          if e.name == "launch.sync"),
        })
    return rows


def summary(rows: Sequence[Dict]) -> Dict[str, Optional[float]]:
    """Means over the launches: milliseconds of each phase per launch
    (``host_ms.<phase>``; None for a phase no launch had), host reads per
    launch (``d2h_reads.sim``), and the share of the launches' time their
    phases cover (``covered``)."""
    if not rows:
        return {}
    out: Dict[str, Optional[float]] = {}
    for name in PHASES:
        per = [r["seconds"].get(name, 0.0) for r in rows]
        had = any(name in r["seconds"] for r in rows)
        out["host_ms." + name.split(".", 1)[1]] = (
            1e3 * sum(per) / len(per) if had else None)
    out["d2h_reads.sim"] = sum(r["arrays"] for r in rows) / len(rows)
    out["covered"] = (sum(sum(r["seconds"].values()) for r in rows)
                      / sum(r["launch_s"] for r in rows))
    return out


def clock_offset(events: Sequence[Event],
                 modules: Sequence[trace.Op]) -> Optional[float]:
    """Seconds the device clock reads ahead of the host's (negative: the
    device reads early), or None where the traced dispatches and the
    entry's modules do not pair one to one."""
    dispatches = sorted((e for e in events if e.name == "launch.dispatch"),
                        key=lambda e: e.t0)
    entries = sorted((m for m in modules if m.name.split("(", 1)[0] == ENTRY),
                     key=lambda m: m.t0)
    if not dispatches or len(dispatches) != len(entries):
        return None
    return min(m.t0 - d.t0 for d, m in zip(dispatches, entries))


def idle_gaps(reduced: "trace.Reduced", events: Sequence[Event],
              offset: Optional[float]) -> List[list]:
    """The longest idle gaps of the device as [name, seconds], each named
    after the phase that overlaps most of it, else after the benchmark's
    innermost span there (``Reduced.host_activity``), once device time
    ``t`` is read as ``t - offset`` on the host's clock; no shift where
    the offset is None."""
    shift = offset or 0.0
    longest = sorted(reduced.gaps(), key=lambda g: g[0] - g[1])[:trace.TOP]
    out = []
    for a, b in longest:
        a0, b0 = a - shift, b - shift
        overlap, name = max(((min(e.t1, b0) - max(e.t0, a0), e.name)
                             for e in events), default=(0.0, None))
        out.append([name if overlap > 0 else reduced.host_activity(a0, b0),
                    b - a])
    return out
