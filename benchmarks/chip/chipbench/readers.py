"""Arithmetic the metric readers share: the window's lane-steps, roofline
shares with their check that the trace is whole, and the idle share."""
from __future__ import annotations

import json
import sys
from typing import Callable, Tuple

from . import work


def sim_span(ctx) -> Tuple[int, float]:
    """(lane-steps, seconds) of the launches that completed in the window,
    from the window's start to the last completion."""
    done = [s for s in ctx.launches if s.t1 <= ctx.window[1]]
    if not done:
        return 0, 0.0
    lane_steps = sum(s.attrs["batch"] * s.attrs["steps"] for s in done)
    return lane_steps, max(s.t1 for s in done) - ctx.window[0]


def roofline(ctx, kernel: str,
             per_launch: Callable[[object], Tuple[int, "work.Work"]]):
    """Percent of the roofline reached by ``kernel`` over the traced
    launches: the least time of their work over the kernel's device time.

    The trace runs from just after the warm-up to the end of the launch
    that overran the window, so every device operation in it belongs to
    one of ``ctx.ran``; counting the whole trace keeps the skew between
    the host's and the device's clocks out of the count.
    ``per_launch(span)`` gives the calls of ``kernel`` that launch makes
    and their work, from the network's shapes and what the launch
    recorded.  The trace has to hold exactly those calls: one that holds
    fewer has lost events, and gives no share (None), as does a trace
    with no call of ``kernel``.  Found and expected calls go to standard
    error either way.
    """
    if ctx.trace is None or not ctx.ran:
        return None
    expected, total = 0, None
    for s in ctx.ran:
        calls, w = per_launch(s)
        expected += calls
        total = w if total is None else total + w
    traced = ctx.trace.host_spans("sim.launch")
    seconds, calls = ctx.trace.kernel(kernel, whole=True)
    print(json.dumps({"stage": "trace", "kernel": kernel,
                      "launches": len(ctx.ran), "traced_launches": len(traced),
                      "calls": calls, "expected_calls": expected}),
          file=sys.stderr, flush=True)
    if len(traced) != len(ctx.ran) or calls != expected or seconds <= 0:
        return None
    least, _bound = work.least_time(total, ctx.peaks)
    return 100.0 * least / seconds


def idle_share(ctx):
    """Percent of the traced window with no operation on the device."""
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
