"""A small recurrent network for the harness's tests: two input
populations, an excitatory population E with a self-loop, an inhibitory
population I (E -> I, I -> E), and an output population fed by E and by
the second input.

Each projection draws a fixed total number of synapses
(``graph.fixed_total_number``), signed integer weights (negative from I),
and a delay per synapse, uniform in [1, ``delay_range``], all from the
configuration's ``structure_seed``.  Neurons follow Eq. 1.  The
configuration file names this module as its ``"generator"`` and
``chipbench/graph.py`` as its ``"reference"``.
"""
from __future__ import annotations

import numpy as np

from chipbench import graph
from chipbench.netgen import NetSpec, Pop, Proj


def generate(cfg: dict) -> NetSpec:
    net = cfg["network"]
    pops = [Pop(p["name"], int(p["size"]), True, rate=float(p["rate"]))
            if p.get("input") else
            Pop(p["name"], int(p["size"]), False, alpha=float(p["alpha"]),
                v_th=float(p["v_th"]))
            for p in net["populations"]]
    sizes = {p.name: p.size for p in pops}
    projs = []
    for k, e in enumerate(net["projections"]):
        S, T, D = sizes[e["pre"]], sizes[e["post"]], int(e["delay_range"])
        rng = np.random.default_rng([int(net["structure_seed"]), k])
        indptr, indices = graph.fixed_total_number(S, T, int(e["synapses"]),
                                                   rng)
        n = indices.size
        values = float(e["sign"]) * rng.integers(1, int(e["max_weight"]) + 1,
                                                 size=n).astype(np.float64)
        projs.append(Proj(
            name=f"{e['pre']}->{e['post']}", pre=e["pre"], post=e["post"],
            n_source=S, n_target=T, indptr=indptr, indices=indices,
            values=values, delays=rng.integers(1, D + 1, size=n),
            delay_range=D))
    back = tuple(k for k, e in enumerate(net["projections"])
                 if e.get("back_edge"))
    return NetSpec(name=net["name"], pops=pops, projs=projs, back_edges=back)


to_program = graph.to_graph_program
