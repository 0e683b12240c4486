"""The whole step's share of the chip's peak: the least time the network's
own work could take (2 operations per synapse per lane-step, Eq. 1 per
updated neuron; the synapse table read once a step, the input spikes
read), over the measured time."""
from chipbench import work
from chipbench.readers import sim_span


def read(ctx):
    if ctx.peaks is None:
        return None
    lane_steps, seconds = sim_span(ctx)
    if not lane_steps:
        return None
    steps = sum(s.attrs["steps"] for s in ctx.launches if s.t1 <= ctx.window[1])
    batch = ctx.launches[0].attrs["batch"]
    spec = ctx.spec
    total = work.network_step(spec.n_synapses, spec.n_neurons - spec.n_input,
                              spec.n_input, batch) * steps
    least, _bound = work.least_time(total, ctx.peaks)
    return 100.0 * least / seconds
