"""Seconds of the switching compile (``compile_network``) in set-up."""


def read(ctx):
    return ctx.spans.total("setup.switching") or None
