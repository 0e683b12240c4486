"""Dense LIF oracle — the executable semantics both paradigms must match.

Eq. (1) of the paper:

    V_i^{t+1} = sum_j W_ji * x_j^{t - d(j,i)} + alpha * V_i^t - z_i^t * V_th
    z_i^t     = H(V_i^t - V_th)          (Heaviside; subtractive reset)

Delays d >= 1.  A ring buffer of ``delay_range + 1`` slots holds future
input currents: the contribution of a spike at time t through a synapse of
delay d lands in slot (t + d), which is consumed when computing V^{t+d+1}.

All weights are int8-magnitude integers, so every accumulation is exact in
float32 and the three executors (reference / serial / parallel) agree
bit-for-bit on the spike trains.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..layer import (
    PROPAGATOR_BITS,
    IafFixed,
    LIFParams,
    SNNLayer,
    is_iaf,
    is_sparse,
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LIFState:
    """Per-layer runtime state (batch leading)."""

    v: jnp.ndarray       # (B, n_target) membrane potential
    z: jnp.ndarray       # (B, n_target) last spike flags (float 0/1)
    ring: jnp.ndarray    # (D+1, B, n_target) future input currents


def init_state(batch: int, n_target: int, delay_range: int) -> LIFState:
    d = delay_range + 1
    return LIFState(
        v=jnp.zeros((batch, n_target), jnp.float32),
        z=jnp.zeros((batch, n_target), jnp.float32),
        ring=jnp.zeros((d, batch, n_target), jnp.float32),
    )


def delay_slot_weights(layer: SNNLayer) -> np.ndarray:
    """(delay_range + 1, n_source, n_target) float32: slice d holds the
    delay-d weights (slice 0 is empty but on a back-edge with delay 0).

    Accepts dense layers and CSR
    :class:`~repro.core.layer.SparseProjection` storage alike — the oracle
    *densifies internally* (it is the brute-force ground truth, not a
    scalable path), so sparse fixtures diff against exactly the same
    dense per-delay tensors their densified twins produce.  Multapses of
    one (delay, pre, post) are summed.
    """
    out = np.zeros(
        (layer.delay_range + 1, layer.n_source, layer.n_target), np.float32
    )
    if is_sparse(layer):
        src, tgt, w, d = layer.coo()
        np.add.at(out, (d, src, tgt), w)
        return out
    conn = layer.connectivity()
    for d in range(1, layer.delay_range + 1):
        m = conn & (layer.delays == d)
        out[d][m] = layer.weights[m]
    return out


def delay_stacked_weights(layer: SNNLayer) -> np.ndarray:
    """(delay_range, n_source, n_target) float32: slice d-1 holds delay-d
    weights (:func:`delay_slot_weights` without its delay-0 slice, which
    only a back-edge may use)."""
    out = delay_slot_weights(layer)
    if out[0].any():
        raise ValueError(f"{layer.name!r} has delay-0 synapses, which only "
                         f"a back-edge of a network may carry")
    return out[1:]


def iaf_psc_exp_int64(i_in, v, i_syn, r, c: IafFixed):
    """One fixed-point ``iaf_psc_exp`` step in numpy int64 arithmetic —
    the same formulas as :func:`repro.kernels.iaf_psc_exp.iaf_psc_exp_ref`,
    with each propagator product taken whole and rounded half up.
    Returns ``(v', i_syn', r', z)`` as int64 arrays."""
    i_in, v, i_syn, r = (np.asarray(a, np.int64) for a in (i_in, v, i_syn, r))
    half = np.int64(1) << (PROPAGATOR_BITS - 1)

    def mul(x, p):
        return (x * np.int64(p) + half) >> PROPAGATOR_BITS

    free = r == 0
    v = np.where(free, mul(v, c.p22) + mul(i_syn, c.p21) + c.v_drive, v)
    r = np.where(free, r, r - 1)
    i_syn = mul(i_syn, c.p11) + (i_in << c.i_bits)
    z = v >= c.v_th
    v = np.where(z, c.v_reset, v)
    r = np.where(z, c.t_ref, r)
    return v, i_syn, r, z.astype(np.int64)


@partial(jax.jit, static_argnames=("delay_range",))
def reference_step(
    w_delay: jnp.ndarray,     # (D, S, T) dense per-delay weights
    state: LIFState,
    x_t: jnp.ndarray,         # (B, S) input spikes at time t (0/1 float)
    t: jnp.ndarray,           # scalar int32 timestep
    *,
    delay_range: int,
    alpha: float = 0.9,
    v_th: float = 1.0,
) -> tuple:
    d_slots = delay_range + 1
    # 1. route spikes to future slots:  ring[(t+d) % slots] += x_t @ W_d
    contrib = jnp.einsum("bs,dst->dbt", x_t, w_delay)        # (D, B, T)
    slot_idx = (t + 1 + jnp.arange(delay_range)) % d_slots   # d = 1..D
    ring = state.ring.at[slot_idx].add(contrib)
    # 2. consume the current slot
    i_t = ring[t % d_slots]
    ring = ring.at[t % d_slots].set(0.0)
    # 3. Eq. (1)
    v_new = i_t + alpha * state.v - state.z * v_th
    z_new = (v_new >= v_th).astype(jnp.float32)
    return LIFState(v=v_new, z=z_new, ring=ring), z_new


def run_reference(
    layer: SNNLayer,
    spikes: np.ndarray,        # (T, B, n_source) 0/1
    lif: LIFParams | None = None,
) -> np.ndarray:
    """Run the oracle over a spike train; returns (T, B, n_target) spikes."""
    lif = lif or layer.lif
    w_delay = jnp.asarray(delay_stacked_weights(layer))
    T, B, _ = spikes.shape
    state = init_state(B, layer.n_target, layer.delay_range)

    def step(carry, inp):
        state, t = carry
        x_t = inp
        state, z = reference_step(
            w_delay, state, x_t, t,
            delay_range=layer.delay_range, alpha=lif.alpha, v_th=lif.v_th,
        )
        return (state, t + 1), z

    (_, _), zs = jax.lax.scan(step, (state, jnp.int32(0)), jnp.asarray(spikes, jnp.float32))
    return np.asarray(zs)


def run_graph_reference(net, spikes: np.ndarray) -> list:
    """Brute-force unrolled application-graph oracle — pure numpy, no scan.

    Simulates an :class:`~repro.core.layer.SNNNetwork` graph (fan-in,
    fan-out, self-loops, recurrent edges) with an explicit Python loop
    over timesteps, dense per-delay weight tensors per projection, and
    the same float32 arithmetic as the fused executor.  Sparse (CSR)
    projections are accepted and **densified internally** via
    :func:`delay_stacked_weights` — the oracle is ground truth, not a
    scalable path, so keep its fixtures small.

    * forward projections see their source population's spikes from the
      **current** timestep (within-step cascade in topological order);
    * **back-edges** see the source's spikes from the **previous**
      timestep (the one-step-delayed feedback path), so a back-edge spike
      of synaptic delay ``d`` arrives ``d + 1`` steps after emission;
    * a population sums the currents of all its in-projections before one
      LIF update (``v' = i + alpha*v - z*v_th``; ``z' = v' >= v_th``), or
      one ``iaf_psc_exp`` step in int64 (:func:`iaf_psc_exp_int64`) from
      its initial potentials;
    * a back-edge may carry delay 0 (arriving one step after emission),
      and multapses are summed per delay;
    * multi-input graphs consume the concatenated ``(T, B, n_input)``
      train — each input population reads its ``net.input_slices``
      columns, exactly like the fused executor.

    All weights are int8-magnitude integers, so every accumulation is an
    exact float32 integer and the result is **bit-identical** to the
    compiled executor on every launch path — this is the differential
    harness's ground truth for non-chain graphs (it shares no code with
    the fused scan).  Returns per-projection trains ``[(T, B, n_post),
    ...]`` — entry ``i`` is projection ``i``'s *target population* spike
    train, matching :meth:`NetworkExecutable.run`.
    """
    spikes = np.asarray(spikes, np.float32)
    T, B, n_in = spikes.shape
    if n_in != net.n_input:
        raise ValueError(
            f"spikes must be (T, B, {net.n_input}); got {spikes.shape}"
        )
    idx = {p.name: i for i, p in enumerate(net.populations)}
    sizes = [p.size for p in net.populations]
    endpoints = net.endpoints
    w_delay = [delay_slot_weights(e) for e in net.projections]
    d_slots = [e.delay_range + 1 for e in net.projections]
    rings = [
        np.zeros((d_slots[i], B, e.n_target), np.float32)
        for i, e in enumerate(net.projections)
    ]
    v = {p: np.zeros((B, sizes[p]), np.float32) for p in range(len(sizes))}
    z = {p: np.zeros((B, sizes[p]), np.float32) for p in range(len(sizes))}
    iaf = {}                                 # pop -> (consts, [v, i, r])
    for p in net.topo_order:
        if p not in set(net.input_indices) and is_iaf(net.population_lif(p)):
            params = net.population_lif(p)
            v0 = np.broadcast_to(params.v0_fixed(sizes[p]), (B, sizes[p]))
            zero = np.zeros((B, sizes[p]), np.int64)
            iaf[p] = (params.fixed(), [v0.astype(np.int64), zero, zero])
    prev = [np.zeros((B, s), np.float32) for s in sizes]
    pop_trains = [np.zeros((T, B, s), np.float32) for s in sizes]
    input_set = set(net.input_indices)
    in_slices = list(zip(net.input_indices, net.input_slices))
    for t in range(T):
        cur = [None] * len(sizes)
        for p, (a, b) in in_slices:
            cur[p] = spikes[t][:, a:b]
        for p in net.topo_order:
            if p in input_set:
                continue
            i_tot = np.zeros((B, sizes[p]), np.float32)
            for ei in net.in_edges[p]:
                e = net.projections[ei]
                src = idx[endpoints[ei][0]]
                x = prev[src] if ei in net.back_edges else cur[src]
                # scatter to future ring slots: delay-d lands at t + d
                contrib = np.einsum(
                    "bs,dst->dbt", x, w_delay[ei]
                ).astype(np.float32)
                ring = rings[ei]
                for d in range(e.delay_range + 1):
                    ring[(t + d) % d_slots[ei]] += contrib[d]
                i_tot += ring[t % d_slots[ei]]
                ring[t % d_slots[ei]] = 0.0
            if p in iaf:
                consts, state = iaf[p]
                out = iaf_psc_exp_int64(i_tot.astype(np.int64), *state, consts)
                state[:] = out[:3]
                cur[p] = out[3].astype(np.float32)
                continue
            lif = net.population_lif(p)
            alpha, v_th = np.float32(lif.alpha), np.float32(lif.v_th)
            v[p] = i_tot + alpha * v[p] - z[p] * v_th
            z[p] = (v[p] >= v_th).astype(np.float32)
            cur[p] = z[p]
        for p in range(len(sizes)):
            pop_trains[p][t] = cur[p]
        prev = cur
    return [pop_trains[idx[post]] for _, post in endpoints]
