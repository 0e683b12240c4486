"""The plain reference: Eq. 1 of the source paper over CSR synapses.

    V_i[t] = sum_j W_ji x_j[t - d_ji] + alpha * V_i[t-1] - z_i[t-1] * v_th
    z_i[t] = V_i[t] >= v_th

Each projection keeps a ring of ``delay_range + 1`` future-current slots:
a source spike at step t through a synapse of delay d lands in slot
t + d.  Populations update in a topological order of the projections, so
a projection sees its source's spikes of the same step.  This is the
semantics of the repository's executor, written again from the
equations: the module uses numpy and scipy only, never densifies a
projection, and shares nothing with the program.

Weights are integers and spikes 0/1, so every synaptic sum is exact and
the membrane update is three float operations per neuron.  ``dtype``
sets the precision of that update: float32 is the configuration's own;
``ml_dtypes.bfloat16`` is the control that the comparison has to fail.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np
import scipy.sparse as sp

from .netgen import NetSpec


def topo_order(spec: NetSpec) -> List[str]:
    """Non-input populations in a topological order of the projections."""
    names = [p.name for p in spec.pops]
    preds = {n: set() for n in names}
    for e in spec.projs:
        preds[e.post].add(e.pre)
    order, done = [], set()
    while len(done) < len(names):
        ready = [n for n in names if n not in done and preds[n] <= done]
        if not ready:
            raise ValueError(f"{spec.name}: projections form a cycle")
        for n in ready:
            done.add(n)
            order.append(n)
    inputs = {p.name for p in spec.inputs}
    return [n for n in order if n not in inputs]


def _delay_matrices(e) -> Dict[int, sp.csr_matrix]:
    """Per delay d: the (n_target, n_source) weight matrix of delay-d synapses."""
    src = e.sources()
    out = {}
    for d in np.unique(e.delays):
        m = e.delays == d
        out[int(d)] = sp.csr_matrix(
            (e.values[m], (e.indices[m], src[m])),
            shape=(e.n_target, e.n_source))
    return out


def simulate(spec: NetSpec, spikes: np.ndarray, dtype=np.float32
             ) -> Dict[str, np.ndarray]:
    """Run the network over ``spikes`` (T, B, n_input) of 0/1.

    Returns every non-input population's train as (T, B, size) uint8.
    """
    spikes = np.asarray(spikes)
    steps, batch, n_in = spikes.shape
    if n_in != spec.n_input:
        raise ValueError(f"spikes must be (T, B, {spec.n_input}); got "
                         f"{spikes.shape}")
    order = topo_order(spec)
    cols = spec.input_slices()
    pops = {p.name: p for p in spec.pops}
    mats = [_delay_matrices(e) for e in spec.projs]
    in_edges = {n: [k for k, e in enumerate(spec.projs) if e.post == n]
                for n in order}
    slots = [e.delay_range + 1 for e in spec.projs]
    rings = [np.zeros((s, e.n_target, batch)) for s, e in zip(slots, spec.projs)]
    v = {n: np.zeros((pops[n].size, batch), dtype) for n in order}
    z = {n: np.zeros((pops[n].size, batch), dtype) for n in order}
    alpha = {n: dtype(pops[n].alpha) for n in order}
    v_th = {n: dtype(pops[n].v_th) for n in order}
    trains = {n: np.zeros((steps, batch, pops[n].size), np.uint8)
              for n in order}
    for t in range(steps):
        cur = {n: spikes[t, :, a:b].T.astype(np.float64)
               for n, (a, b) in cols.items()}
        for n in order:
            current = np.zeros((pops[n].size, batch))
            for k in in_edges[n]:
                e = spec.projs[k]
                x = cur[e.pre]
                ring = rings[k]
                for d, w in mats[k].items():
                    ring[(t + d) % slots[k]] += w @ x
                current += ring[t % slots[k]]
                ring[t % slots[k]] = 0.0
            v[n] = current.astype(dtype) + alpha[n] * v[n] - z[n] * v_th[n]
            z[n] = (v[n] >= v_th[n]).astype(dtype)
            cur[n] = z[n].astype(np.float64)
            trains[n][t] = z[n].T
    return trains
