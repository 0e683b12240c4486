"""Recurrent, multi-input networks in CSR form: the program's graph and an
event-driven plain reference, for configurations that bring their own
generator.

A generator (the module a configuration names under ``"generator"``)
draws a ``NetSpec`` whose projections are CSR arrays and which declares
its back-edges; ``to_graph_program`` hands the program those arrays as
``SparseProjection``s and never builds an (S, T) array.  A configuration
whose neurons follow Eq. 1 names this module as its ``"reference"``
(``simulate``); one with other dynamics passes its own ``update`` to
``simulate_graph`` from its own file.

Timing, as the program's executor documents it (``SNNNetwork``): a spike
emitted at step t through a synapse of delay d is part of its target's
input at step t + d, and at t + d + ``BACK_EDGE_LAG`` (one step later)
where the projection is a declared back-edge, which the program routes
through its one-step feedback ring.  Every delay is at least 1, so no
spike reaches a population in the step it was emitted.

``simulate_graph`` uses numpy only and nothing of the program.  Each step
it updates the populations in a topological order of the forward edges,
then, for each projection, takes the CSR rows of the sources that spiked
and adds their weights into the target's ring of future currents: its
work follows the spikes, not the synapse count.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import numpy as np

from .netgen import NetSpec

#: Extra steps a spike takes across a declared back-edge.
BACK_EDGE_LAG = 1


def fixed_total_number(n_source: int, n_target: int, n_synapses: int,
                       rng: np.random.Generator
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """(indptr, indices): ``n_synapses`` distinct (source, target) pairs
    drawn uniformly, rows sorted, columns sorted within a row.

    The program's CSR holds one synapse per pair, so no pair is drawn
    twice (no multapses).  The draw holds the pairs alone, never an
    (S, T) array.
    """
    if not 0 <= n_synapses <= n_source * n_target:
        raise ValueError(f"{n_synapses} synapses do not fit "
                         f"{n_source} x {n_target}")
    flat = np.sort(rng.choice(n_source * n_target, size=n_synapses,
                              replace=False))
    src, tgt = np.divmod(flat, n_target)
    indptr = np.zeros(n_source + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n_source), out=indptr[1:])
    return indptr, tgt.astype(np.int64)


def to_graph_program(spec: NetSpec):
    """The program's graph-form ``SNNNetwork`` of ``spec``, every projection
    a ``SparseProjection`` of the spec's own CSR arrays.

    The program treats the declared back-edges as such; a back-edge it
    would add of its own (a cycle the spec leaves undeclared) is refused,
    as is an input population the program does not read as one.
    """
    from repro.core.layer import (LIFParams, Population, SNNNetwork,
                                  SparseProjection)

    lif = {p.name: LIFParams(alpha=p.alpha, v_th=p.v_th)
           for p in spec.pops if not p.is_input}
    net = SNNNetwork(
        populations=[Population(p.name, p.size, lif.get(p.name))
                     for p in spec.pops],
        projections=[SparseProjection(
            n_source=e.n_source, n_target=e.n_target, indptr=e.indptr,
            indices=e.indices, values=e.values, delay_values=e.delays,
            delay_range=e.delay_range, lif=lif[e.post], name=e.name,
            pre=e.pre, post=e.post) for e in spec.projs],
        forced_back_edges=spec.back_edges, name=spec.name)
    if net.back_edges != frozenset(spec.back_edges):
        raise ValueError(f"{spec.name}: the program's back-edges "
                         f"{sorted(net.back_edges)} are not the declared "
                         f"{sorted(spec.back_edges)}")
    if [p.name for p in net.input_populations] != [p.name for p in spec.inputs]:
        raise ValueError(f"{spec.name}: the program's inputs are not the "
                         f"spec's")
    return net


def forward_order(spec: NetSpec) -> List[str]:
    """Non-input populations in a topological order of the projections that
    are not declared back-edges; a cycle among those is refused."""
    back = set(spec.back_edges)
    preds: Dict[str, set] = {p.name: set() for p in spec.pops}
    for k, e in enumerate(spec.projs):
        if k not in back:
            preds[e.post].add(e.pre)
    order: List[str] = []
    while len(order) < len(preds):
        ready = [n for n in preds if n not in order and preds[n] <= set(order)]
        if not ready:
            raise ValueError(f"{spec.name}: projections that are not declared "
                             f"back-edges form a cycle")
        order += ready
    inputs = {p.name for p in spec.inputs}
    return [n for n in order if n not in inputs]


def eq1(state: dict, current: np.ndarray, params: dict, dtype):
    """Eq. 1 of the source paper, on (B, n) maps:

        v' = i + alpha * v - z * v_th;   z' = v' >= v_th

    ``state`` holds ``v`` and ``z``, and is empty before the first step.
    Returns the new state and the spikes, in ``dtype``.
    """
    zero = np.zeros(current.shape, dtype)
    v, z = state.get("v", zero), state.get("z", zero)
    alpha, v_th = dtype(params["alpha"]), dtype(params["v_th"])
    v = current.astype(dtype) + alpha * v - z * v_th
    z = (v >= v_th).astype(dtype)
    return {"v": v, "z": z}, z


def rows(indptr: np.ndarray, sources: np.ndarray) -> np.ndarray:
    """Synapse indices of the CSR rows ``sources``, one row after another."""
    starts = indptr[sources]
    lengths = indptr[sources + 1] - starts
    first = np.cumsum(lengths) - lengths      # each row's first output slot
    return np.repeat(starts - first, lengths) + np.arange(int(lengths.sum()))


def _route(e, fired: np.ndarray, ring: np.ndarray, t: int) -> None:
    """Add the synapses of every source that fired (``fired``: (B, S)) at
    step ``t`` into ``ring`` (slots, B, T) at slot t + delay."""
    lanes, sources = np.nonzero(fired)
    if not sources.size:
        return
    syn = rows(e.indptr, sources)
    lane = np.repeat(lanes, e.indptr[sources + 1] - e.indptr[sources])
    slot = (t + e.delays[syn]) % ring.shape[0]
    np.add.at(ring, (slot, lane, e.indices[syn]), e.values[syn])


def simulate_graph(spec: NetSpec, spikes: np.ndarray, dtype=np.float32,
                   update: Callable = eq1) -> Dict[str, np.ndarray]:
    """Run ``spec`` over ``spikes`` (T, B, n_input) of 0/1, the input
    populations' columns in declared order.

    ``update(state, current, params, dtype) -> (state, spikes)`` is the
    neuron model; ``params`` is a population's ``alpha`` and ``v_th``
    with its ``params`` over them.  Synaptic currents are summed in
    float64 (exact for integer weights), then handed to ``update``.
    Returns every non-input population's train as (T, B, size) uint8.
    """
    spikes = np.asarray(spikes)
    steps, batch, n_in = spikes.shape
    if n_in != spec.n_input:
        raise ValueError(f"spikes must be (T, B, {spec.n_input}); got "
                         f"{spikes.shape}")
    order = forward_order(spec)
    pops = {p.name: p for p in spec.pops}
    back = set(spec.back_edges)
    lag = [BACK_EDGE_LAG if k in back else 0 for k in range(len(spec.projs))]
    reach = {n: 0 for n in order}
    for k, e in enumerate(spec.projs):
        reach[e.post] = max(reach[e.post], e.delay_range + lag[k])
    rings = {n: np.zeros((reach[n] + 1, batch, pops[n].size)) for n in order}
    params = {n: {"alpha": pops[n].alpha, "v_th": pops[n].v_th,
                  **pops[n].params} for n in order}
    state: Dict[str, dict] = {n: {} for n in order}
    trains = {n: np.zeros((steps, batch, pops[n].size), np.uint8)
              for n in order}
    cols = spec.input_slices()
    for t in range(steps):
        fired = {n: spikes[t, :, a:b] for n, (a, b) in cols.items()}
        for n in order:
            ring = rings[n]
            slot = t % ring.shape[0]
            current = ring[slot].copy()
            ring[slot] = 0.0
            state[n], fired[n] = update(state[n], current, params[n], dtype)
            trains[n][t] = fired[n]
        for k, e in enumerate(spec.projs):
            _route(e, fired[e.pre], rings[e.post], t + lag[k])
    return trains


#: The reference of a configuration whose neurons follow Eq. 1.
simulate = simulate_graph
