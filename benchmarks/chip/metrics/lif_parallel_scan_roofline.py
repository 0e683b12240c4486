"""``lif_parallel_scan``'s share of its roofline: the least time of the
affine scans the temporal path ran (one call per population per
fixed-point pass, as each launch recorded its passes, over steps x batch x
neurons; 2 operations and 8 bytes an element) over the kernel's device
time in the trace."""
from chipbench import work
from chipbench.readers import roofline

KERNEL = "affine_scan_pallas"


def read(ctx):
    # the program records passes by population index, in declared order
    sizes = [p.size for p in ctx.spec.pops]

    def per_launch(s):
        passes = s.attrs["iterations"]
        w = work.ZERO
        for pop, n in passes.items():
            w = w + work.lif_parallel_scan(
                s.attrs["steps"], s.attrs["batch"] * sizes[pop]) * n
        return sum(passes.values()), w

    return roofline(ctx, KERNEL, per_launch)
