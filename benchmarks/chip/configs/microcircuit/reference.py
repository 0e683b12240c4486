"""The microcircuit's plain reference: an event-driven numpy loop with an
``iaf_psc_exp`` update of its own, nothing of the program.

``simulate(spec, spikes, dtype=np.float32)``: float32 means the
configuration's stated fixed point, applied in numpy int64 with every
value asserted to fit int32 (an overflow fails the check instead of
matching by accident); bfloat16 (the control) integrates the same
equations in physical units in bfloat16.  The update follows NEST's
order: the potential evolves from the current before this step's input
(held during the refractory count), the current decays and takes the
step's input, a potential at or over threshold spikes, resets and starts
the refractory count.

The loop is ``chipbench/graph.simulate_graph``'s, with its timing: each
step updates the populations in ``graph.forward_order``, then adds the
CSR rows of the sources that fired (``graph.rows``) into each target's
ring of future inputs at slot t + d, one step later
(``graph.BACK_EDGE_LAG``) across a declared back-edge.  Its rings are
int64 and a target's synapses of one step go in with one ``np.add.at``,
so its work follows the spikes at numpy's integer speed: the 3e8
synapses' volley of a trial's first step is routed in seconds.
"""
from __future__ import annotations

import numpy as np

from chipbench import graph

P_BITS = 31
INT32 = (-(1 << 31), (1 << 31) - 1)
#: Largest current a step may add to a float32 ring exactly.
EXACT_F32 = 1 << 24


def _fits(name: str, x: np.ndarray) -> np.ndarray:
    if x.size and (x.min() < INT32[0] or x.max() > INT32[1]):
        raise OverflowError(f"iaf_psc_exp: {name} leaves int32 "
                            f"[{x.min()}, {x.max()}]")
    return x


def _mul(x: np.ndarray, p: int) -> np.ndarray:
    return (x * np.int64(p) + (np.int64(1) << (P_BITS - 1))) >> P_BITS


def iaf_fixed(state: dict, current: np.ndarray, params: dict, dtype):
    """One step in the stated fixed point (int64 arithmetic)."""
    if not state:
        v0 = np.broadcast_to(params["v0"], current.shape).astype(np.int64)
        zero = np.zeros(current.shape, np.int64)
        state = {"v": v0, "i": zero, "r": zero}
    if np.abs(current).max(initial=0) >= EXACT_F32:
        raise OverflowError("iaf_psc_exp: a step's input leaves the "
                            "exact float32 range of the rings")
    inp = np.asarray(current, np.int64)
    v, i, r = state["v"], state["i"], state["r"]
    free = r == 0
    v = _fits("potential", np.where(
        free, _mul(v, params["p22"]) + _mul(i, params["p21"])
        + params["v_drive"], v))
    r = np.where(free, r, r - 1)
    i = _fits("current", _mul(i, params["p11"])
              + _fits("input", inp << params["i_bits"]))
    z = v >= params["v_th"]
    v = np.where(z, params["v_reset"], v)
    r = np.where(z, params["t_ref"], r)
    return {"v": v, "i": i, "r": r}, z.astype(np.uint8)


def iaf_float(state: dict, current: np.ndarray, params: dict, dtype):
    """One step of the same equations in physical units, in ``dtype``."""
    if not state:
        v0 = (np.asarray(params["v0_mV"]) - params["neuron"]["E_L"])
        v0 = np.broadcast_to(v0, current.shape).astype(dtype)
        zero = np.zeros(current.shape, dtype)
        state = {"v": v0, "i": zero, "r": np.zeros(current.shape, np.int64)}
    c = lambda x: dtype(x)
    v, i, r = state["v"], state["i"], state["r"]
    free = r == 0
    v_free = (c(params["P22"]) * v + c(params["P21"]) * i
              + c(params["P20"] * params["I_e"])).astype(dtype)
    v = np.where(free, v_free, v).astype(dtype)
    r = np.where(free, r, r - 1)
    inp = (current * params["weight_pa"]).astype(dtype)
    i = (c(params["P11"]) * i + inp).astype(dtype)
    nrn = params["neuron"]
    z = v >= c(nrn["V_th"] - nrn["E_L"])
    v = np.where(z, c(nrn["V_reset"] - nrn["E_L"]), v).astype(dtype)
    r = np.where(z, params["t_ref"], r)
    return {"v": v, "i": i, "r": r}, z.astype(np.uint8)


def _route(projs, fired: dict, ring: np.ndarray, t: int) -> None:
    """Add the synapses of every source in ``fired`` (name -> (B, S)) of
    the projections ``projs`` (each with its lag) into ``ring`` (slots, B,
    n) at slot t + lag + delay, in one integer ``np.add.at``."""
    slots, batch, n = ring.shape
    flat, weights = [], []
    for e, lag in projs:
        lanes, sources = np.nonzero(fired[e.pre])
        if not sources.size:
            continue
        syn = graph.rows(e.indptr, sources)
        lane = np.repeat(lanes, e.indptr[sources + 1] - e.indptr[sources])
        slot = (t + lag + e.delays[syn].astype(np.int64)) % slots
        flat.append((slot * batch + lane) * n + e.indices[syn])
        weights.append(e.values[syn].astype(np.int64))
    if flat:
        np.add.at(ring.reshape(-1), np.concatenate(flat),
                  np.concatenate(weights))


def simulate(spec, spikes: np.ndarray, dtype=np.float32):
    """Every non-input population's train, (T, B, n) uint8."""
    update = iaf_fixed if dtype == np.float32 else iaf_float
    spikes = np.asarray(spikes)
    steps, batch, n_in = spikes.shape
    if n_in != spec.n_input:
        raise ValueError(f"spikes must be (T, B, {spec.n_input}); got "
                         f"{spikes.shape}")
    order = graph.forward_order(spec)
    pops = {p.name: p for p in spec.pops}
    back = set(spec.back_edges)
    into = {n: [] for n in order}
    for k, e in enumerate(spec.projs):
        into[e.post].append((e, graph.BACK_EDGE_LAG if k in back else 0))
    rings = {n: np.zeros((1 + max(e.delay_range + lag for e, lag in into[n]),
                          batch, pops[n].size), np.int64) for n in order}
    params = {n: pops[n].params for n in order}
    state = {n: {} for n in order}
    trains = {n: np.zeros((steps, batch, pops[n].size), np.uint8)
              for n in order}
    cols = spec.input_slices()
    for t in range(steps):
        fired = {n: spikes[t, :, a:b] for n, (a, b) in cols.items()}
        for n in order:
            ring = rings[n]
            slot = t % ring.shape[0]
            current = ring[slot].copy()
            ring[slot] = 0
            state[n], fired[n] = update(state[n], current, params[n], dtype)
            trains[n][t] = fired[n]
        for n in order:
            _route(into[n], fired, rings[n], t)
    return trains
