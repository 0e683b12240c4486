"""Synaptic events the spike-driven serial form routed in the window's
launches, per second of the window (its start to the last completion,
as ``sim_steps_per_s`` times it).

Read from the program's ``events.routed`` counter: the launch program
counts the synapses it routes on the device, and the executable keeps
each read launch's totals (``launch_counts``, in launch order), so the
last ``len(ctx.ran)`` of them are the traced period's launches.  A
program that keeps no such counts gives no value.
"""
from chipbench.readers import sim_span


def read(ctx):
    counts = getattr(ctx.exe, "launch_counts", None)
    if not counts or len(counts) < len(ctx.ran) or not ctx.launches:
        return None
    ran = counts[len(counts) - len(ctx.ran):]
    events = sum(c["events.routed"] for c in ran[:len(ctx.launches)])
    _, seconds = sim_span(ctx)
    return events / seconds if seconds > 0 else None
