"""``spike_wdm_matmul``'s share of its roofline: the least time of its
calls' int8 products (one call per parallel projection per step, over the
weight-delay map's unpadded rows and columns and the launch's lanes) over
the kernel's device time in the trace."""
from chipbench import work
from chipbench.readers import roofline

KERNEL = "spike_wdm_matmul_pallas"


def _wdm_shapes(exe):
    """(rows, columns) of each parallel projection's weight-delay map."""
    return [tuple(int(d) for d in params[0].shape)
            for meta, params in zip(exe.metas, exe.params)
            if meta.paradigm == "parallel" and params[0].shape[1]]


def read(ctx):
    shapes = _wdm_shapes(ctx.exe)
    if not shapes:
        return None

    def per_launch(s):
        w = work.Work(0.0, 0.0, int8=True)
        for m, cols in shapes:
            w = w + work.spike_wdm_matmul(m, cols, s.attrs["batch"])
        return len(shapes) * s.attrs["steps"], w * s.attrs["steps"]

    return roofline(ctx, KERNEL, per_launch)
