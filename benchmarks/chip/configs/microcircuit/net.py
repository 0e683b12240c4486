"""The Potjans & Diesmann (2014) cortical microcircuit, drawn from the
configuration ``configs/microcircuit.json`` as NEST's ``Potjans_2014``
example draws it, for the benchmark's generator slot.

Eight recurrent populations of ``iaf_psc_exp`` neurons (L2/3, L4, L5, L6;
excitatory and inhibitory each) and a thalamic input population.  Each
projection with connection probability C > 0 holds a fixed total number
of synapses, K = ln(1 - C) / ln(1 - 1 / (N_pre N_post)) (scaled by
``N_scaling`` and ``K_scaling`` as the example scales them), every one
drawn with its source and target uniform and independent: pairs repeat
(multapses), as NEST's ``fixed_total_number`` draws them.  Weights are
normal around the PSC of a 0.15 mV PSP (g = -4 for inhibitory sources,
doubled for L4E -> L2/3E), relative sd 0.1, redrawn past zero, then held
as integers of the configuration's weight quantum (int8, clipped at 127:
``clipped_weights`` counts them).  Delays are normal (relative sd 0.5),
redrawn below one step, rounded to the step.  The Poisson background is
NEST's DC compensation (``poisson_input`` false): I_e = bg_rate K_ext
w_ext tau_syn.  Initial potentials are N(V0_mean, V0_std).

The program delivers a spike across a back-edge one step late, so a
projection the program takes as a back-edge (a self-loop, or one onto a
population declared earlier) stores ``d - 1`` for a published delay
``d``; both sides then deliver it at exactly ``d``.

Everything is drawn from ``structure_seed``, one generator per
projection, vectorised and in narrow dtypes (int32 targets, int8
weights, int16 delays); nothing (S, T) is built.  ``fixed`` gives the
fixed-point constants both the program (through ``to_program``) and the
reference (``configs/microcircuit/reference.py``) use.
"""
from __future__ import annotations

import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench.netgen import NetSpec, Pop, Proj

#: ``iaf_psc_exp`` constants the reference reads from ``Pop.params``.
FIXED_KEYS = ("p22", "p21", "p11", "v_drive", "v_th", "v_reset", "t_ref",
              "i_bits")


def psc_over_psp(c_m: float, tau_m: float, tau_syn: float) -> float:
    """pA of PSC per mV of PSP peak (NEST example ``helpers``)."""
    sub = 1.0 / (tau_syn - tau_m)
    pre = tau_m * tau_syn / c_m * sub
    frac = (tau_m / tau_syn) ** sub
    return 1.0 / (pre * (frac ** tau_m - frac ** tau_syn))


def _round_half_up(x):
    return np.floor(np.asarray(x, np.float64) + 0.5).astype(np.int64)


def fixed(neuron: dict, precision: dict, i_e: float) -> dict:
    """The integer constants of the update (the configuration's
    ``precision`` states the formats): propagators with ``p_bits``
    fraction bits, potential relative to E_L in 2**-v_bits mV, current in
    2**-i_bits weight quanta, rounding half up."""
    h, tm, ts = neuron["dt"], neuron["tau_m"], neuron["tau_syn"]
    c_m = neuron["C_m"]
    p22 = np.exp(-h / tm)
    p11 = np.exp(-h / ts)
    p21 = ts * tm / (c_m * (tm - ts)) * (p22 - p11)
    p20 = tm / c_m * (1.0 - p22)
    one = 2.0 ** precision["p_bits"]
    v_unit = 2.0 ** precision["v_bits"]
    scale21 = precision["weight_pa"] * 2.0 ** (precision["v_bits"]
                                               - precision["i_bits"])
    return {
        "p22": int(_round_half_up(p22 * one)),
        "p21": int(_round_half_up(p21 * scale21 * one)),
        "p11": int(_round_half_up(p11 * one)),
        "v_drive": int(_round_half_up(p20 * i_e * v_unit)),
        "v_th": int(_round_half_up((neuron["V_th"] - neuron["E_L"]) * v_unit)),
        "v_reset": int(_round_half_up((neuron["V_reset"] - neuron["E_L"])
                                      * v_unit)),
        "t_ref": int(round(neuron["t_ref"] / h)),
        "i_bits": int(precision["i_bits"]),
        # the same constants in physical units, for a float update
        "P22": float(p22), "P21": float(p21), "P11": float(p11),
        "P20": float(p20), "I_e": float(i_e),
    }


def _normal(rng, mean, sd, n):
    """``n`` normal draws in float32 (half the time of float64's)."""
    x = rng.standard_normal(n, dtype=np.float32)
    x *= np.float32(sd)
    x += np.float32(mean)
    return x


def _redraw_normal(rng, mean, sd, n, low=None, high=None):
    """``n`` normal draws, each redrawn until it lies in [low, high]."""
    x = _normal(rng, mean, sd, n)
    while True:
        bad = np.zeros(n, bool)
        if low is not None:
            bad |= x < low
        if high is not None:
            bad |= x > high
        k = int(bad.sum())
        if not k:
            return x
        x[bad] = _normal(rng, mean, sd, k)


def sizes(net: dict) -> dict:
    """Scaled population sizes, thalamus included."""
    s = net["N_scaling"]
    out = {name: int(round(n * s)) for name, n in
           zip(net["populations"], net["full_num_neurons"])}
    out[net["thalamus"]["name"]] = int(round(net["thalamus"]["size"] * s))
    return out


def synapse_counts(net: dict):
    """(recurrent (8, 8) target x source, thalamic (8,)) synapse totals at
    the configuration's scale, as the NEST example rounds them."""
    n_full = np.asarray(net["full_num_neurons"], np.float64)
    c = np.asarray(net["conn_probs"], np.float64)
    prod = np.outer(n_full, n_full)
    k = np.log(1.0 - c) / np.log((prod - 1.0) / prod)
    k = np.round(k * net["N_scaling"] * net["K_scaling"]).astype(np.int64)
    th = net["thalamus"]
    c_th = np.asarray(th["conn_probs"], np.float64)
    prod_th = n_full * th["size"]
    k_th = np.log(1.0 - c_th) / np.log((prod_th - 1.0) / prod_th)
    k_th = np.round(k_th * net["N_scaling"] * net["K_scaling"]).astype(np.int64)
    return k, k_th


def psc_means(net: dict) -> tuple:
    """(recurrent (8, 8) PSC means in pA, external PSC, DC per population),
    K_scaling's adjustment included (NEST example ``helpers``)."""
    nrn = net["neuron"]
    per_psp = psc_over_psp(nrn["C_m"], nrn["tau_m"], nrn["tau_syn"])
    psp = np.full((8, 8), net["PSP_exc_mean"])
    psp[:, 1::2] *= net["g"]
    psp[0, 2] *= 2.0
    psc = psp * per_psp
    psc_ext = net["PSP_exc_mean"] * per_psp
    k_ext = np.round(np.asarray(net["K_ext"]) * net["K_scaling"])
    dc = net["bg_rate"] * k_ext * psc_ext * nrn["tau_syn"] * 0.001
    ks = net["K_scaling"]
    if ks != 1:
        n_full = np.asarray(net["full_num_neurons"], np.float64)
        c = np.asarray(net["conn_probs"], np.float64)
        prod = np.outer(n_full, n_full)
        k_full = np.log(1.0 - c) / np.log((prod - 1.0) / prod)
        indegree = k_full / n_full[:, None]
        rates = np.asarray(net["full_mean_rates"])
        input_rec = np.sum(psc * indegree * rates, axis=1)
        dc = dc + 0.001 * nrn["tau_syn"] * (1.0 - np.sqrt(ks)) * input_rec
        psc, psc_ext = psc / np.sqrt(ks), psc_ext / np.sqrt(ks)
    return psc, psc_ext, dc


def _draw(rng, n_src, n_tgt, k, w_mean, w_sd, d_mean, d_sd, quantum, dt,
          back):
    """One projection: CSR by source, multapses allowed."""
    counts = rng.multinomial(k, np.full(n_src, 1.0 / n_src))
    indptr = np.zeros(n_src + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    indices = rng.integers(0, n_tgt, k, dtype=np.int32)
    if w_mean >= 0:
        w = _redraw_normal(rng, w_mean, w_sd, k, low=0.0)
    else:
        w = _redraw_normal(rng, w_mean, w_sd, k, high=0.0)
    w /= np.float32(quantum)
    w += np.float32(0.5)
    q = np.floor(w, out=w)                     # rounded half up
    clipped = int(np.count_nonzero(np.abs(q) > 127))
    np.clip(q, -127, 127, out=q)
    # a weight that rounds to 0 quanta is no synapse of the CSR; it
    # becomes the smallest of its sign (none at these means: 0 is 10 sd
    # away)
    q[q == 0] = np.sign(w_mean)
    d = _redraw_normal(rng, d_mean, d_sd, k, low=dt)
    d /= np.float32(dt)
    d += np.float32(0.5)
    d = np.maximum(np.floor(d, out=d), 1) - (1 if back else 0)
    return (indptr, indices, q.astype(np.int8), d.astype(np.int16),
            clipped)


def generate(cfg: dict) -> NetSpec:
    net = cfg["network"]
    prec = cfg["precision_formats"]
    nrn = net["neuron"]
    names = list(net["populations"])
    th = net["thalamus"]
    size = sizes(net)
    k_rec, k_th = synapse_counts(net)
    psc, psc_ext, dc = psc_means(net)
    quantum = prec["weight_pa"] / math.sqrt(net["K_scaling"])
    prec_run = dict(prec, weight_pa=quantum)
    seed = int(net["structure_seed"])
    rng0 = np.random.default_rng([seed, 999])
    pops = [Pop(th["name"], size[th["name"]], True, rate=float(th["rate"]))]
    for a, name in enumerate(names):
        consts = fixed(nrn, prec_run, float(dc[a]))
        v0 = rng0.normal(nrn["V0_mean"], nrn["V0_std"], size[name])
        consts["v0_mV"] = v0
        consts["v0"] = _round_half_up((v0 - nrn["E_L"])
                                      * 2.0 ** prec["v_bits"])
        consts["weight_pa"] = quantum
        consts["v_bits"] = int(prec["v_bits"])
        consts["neuron"] = nrn
        pops.append(Pop(name, size[name], False, params=consts))
    projs, back = [], []
    clipped = 0
    dt = nrn["dt"]
    order = {name: a for a, name in enumerate(names)}
    pairs = [(th["name"], names[t], int(k_th[t]), psc_ext, net["delay_th_mean"])
             for t in range(8) if k_th[t] > 0]
    pairs += [(names[s], names[t], int(k_rec[t, s]), psc[t, s],
               net["delay_exc_mean"] if s % 2 == 0 else net["delay_inh_mean"])
              for s in range(8) for t in range(8) if k_rec[t, s] > 0]
    backs = [pre in order and order[post] <= order[pre]
             for pre, post, _, _, _ in pairs]

    def draw(i):
        pre, post, k, w_mean, d_mean = pairs[i]
        return _draw(np.random.default_rng([seed, i]), size[pre], size[post],
                     k, w_mean, abs(w_mean) * net["weight_rel_std"], d_mean,
                     d_mean * net["delay_rel_std"], quantum, dt, backs[i])

    # each projection has a generator of its own, so drawing them side by
    # side (numpy's draws and arithmetic release the GIL) gives the same
    # arrays in any order; the largest are started first
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        drawn = dict(zip(
            sorted(range(len(pairs)), key=lambda i: -pairs[i][2]),
            pool.map(draw, sorted(range(len(pairs)),
                                  key=lambda i: -pairs[i][2]))))
    for i, (pre, post, _, _, _) in enumerate(pairs):
        indptr, indices, q, d, c = drawn.pop(i)
        clipped += c
        projs.append(Proj(
            name=f"{pre}->{post}", pre=pre, post=post, n_source=size[pre],
            n_target=size[post], indptr=indptr, indices=indices, values=q,
            delays=d, delay_range=int(max(1, d.max(initial=1)))))
        if backs[i]:
            back.append(i)
    spec = NetSpec(name=net["name"], pops=pops, projs=projs,
                   back_edges=tuple(back))
    spec.clipped_weights = clipped
    return spec


def to_program(spec: NetSpec):
    """The program's graph-form ``SNNNetwork``: ``iaf_psc_exp`` populations
    and the spec's CSR arrays as ``SparseProjection``s, back-edges checked
    to be the program's own (as ``graph.to_graph_program`` checks them)."""
    from repro.core.layer import (IafPscExpParams, Population, SNNNetwork,
                                  SparseProjection)

    pops = []
    for p in spec.pops:
        if p.is_input:
            pops.append(Population(p.name, p.size))
            continue
        n, c = p.params["neuron"], p.params
        pops.append(Population(p.name, p.size, IafPscExpParams(
            tau_m=n["tau_m"], tau_syn=n["tau_syn"], c_m=n["C_m"],
            t_ref=c["t_ref"], e_l=n["E_L"], v_reset=n["V_reset"],
            v_th=n["V_th"], i_e=c["I_e"], dt=n["dt"],
            weight_pa=c["weight_pa"], v_bits=c["v_bits"],
            i_bits=c["i_bits"], v0=c["v0_mV"])))
    net = SNNNetwork(
        populations=pops,
        projections=[SparseProjection(
            n_source=e.n_source, n_target=e.n_target, indptr=e.indptr,
            indices=e.indices, values=e.values, delay_values=e.delays,
            delay_range=e.delay_range, name=e.name, pre=e.pre, post=e.post)
            for e in spec.projs],
        forced_back_edges=spec.back_edges, name=spec.name)
    if net.back_edges != frozenset(spec.back_edges):
        raise ValueError(f"{spec.name}: the program's back-edges "
                         f"{sorted(net.back_edges)} are not the declared "
                         f"{sorted(spec.back_edges)}")
    return net
