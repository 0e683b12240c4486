"""``lif_update``'s share of its roofline: the least time of its calls'
work (one call per population per step; 5 operations and 20 bytes per
neuron per lane, lane padding not counted) over the kernel's device time
in the trace."""
from chipbench import work
from chipbench.readers import roofline

KERNEL = "lif_update_pallas"


def read(ctx):
    pops = [p for p in ctx.spec.pops if not p.is_input]

    def per_launch(s):
        w = sum((work.lif_update(p.size, s.attrs["batch"]) for p in pops),
                work.ZERO)
        return len(pops) * s.attrs["steps"], w * s.attrs["steps"]

    return roofline(ctx, KERNEL, per_launch)
