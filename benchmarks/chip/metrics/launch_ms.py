"""Mean host-clock time of one launch of the window: dispatch, the run on
the device, outputs on the host."""


def read(ctx):
    if not ctx.launches:
        return None
    return 1e3 * sum(s.seconds for s in ctx.launches) / len(ctx.launches)
