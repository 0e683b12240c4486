"""Batch x timesteps of every launch whose outputs reached the host inside
the window, over the time from the window's start to the last of them."""
from chipbench.readers import sim_span


def read(ctx):
    lane_steps, seconds = sim_span(ctx)
    return lane_steps / seconds if lane_steps else None
