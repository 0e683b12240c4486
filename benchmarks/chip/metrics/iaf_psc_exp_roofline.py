"""``iaf_psc_exp``'s share of its roofline: the least time of its calls'
work over the kernel's device time in the trace.

One call per ``iaf_psc_exp`` population per step, on (lane, neuron)
pairs: 96 int32 operations per pair (three propagator products of 28
operations each, built from 16-bit limbs, and 12 more for the threshold,
reset and refractory count), and 8 bytes: the step's synaptic input read
and its spike written.  The potential, current and refractory count are
the launch program's carry and stay in on-chip memory from step to step
(counted at 32 bytes a pair, a traced run read 138%: the state does not
cross HBM).  Lane padding is not counted.  The work functions live here:
the kernel is this configuration's alone.
"""
from chipbench import work
from chipbench.readers import roofline

KERNEL = "iaf_psc_exp_pallas"
#: int32 operations of one (lane, neuron) update
OPS = 96.0
#: bytes one (lane, neuron) update moves through HBM
BYTES = 8.0


def iaf_psc_exp(n: int, batch: int) -> work.Work:
    """One kernel call over ``n`` neurons at ``batch`` lanes."""
    return work.Work(OPS * n * batch, BYTES * n * batch)


def _iaf_pops(spec):
    return [p for p in spec.pops if not p.is_input and "p22" in p.params]


def read(ctx):
    pops = _iaf_pops(ctx.spec)
    if not pops:
        return None

    def per_launch(s):
        w = sum((iaf_psc_exp(p.size, s.attrs["batch"]) for p in pops),
                work.ZERO)
        return len(pops) * s.attrs["steps"], w * s.attrs["steps"]

    return roofline(ctx, KERNEL, per_launch)
