"""Seconds of the warm-up: the first launches of every shape the window
uses, which compile or load each program from the persistent cache."""


def read(ctx):
    return ctx.spans.total("setup.warmup") or None
