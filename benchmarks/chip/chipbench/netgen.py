"""Networks of the benchmark's configurations, generated from their files;
the default generator, and the ``NetSpec`` every generator returns.

The benchmark owns its networks: a configuration file states the recipe
(population sizes, densities, delays, LIF constants) and a generator
draws the synapses.  Both the system under test and the plain reference
are given the same arrays, so neither depends on how the other stores
them.  This module draws chains; a configuration that names another
``"generator"`` (``chipbench/spec.py``) brings its own, such as a
recurrent graph through ``chipbench/graph.py``.

The whole network (which synapses exist, their delays, signs and
magnitudes) is drawn from the configuration's fixed ``structure_seed``,
as a deployed classifier has one set of weights: compiled shapes (WDM
columns, ELL row widths, event-form row counts) are the same in every
run, and so is the work the temporal path's fixed point does, which
follows the weights (6 to 209 passes a launch over weights drawn from six
other seeds).  The run's ``--seed`` draws the inputs.

The sampler copies the repository's own generator draw for draw
(``repro.core.layer.random_layer``, with per-source "axonal" delays);
``tests/test_chipbench_reference.py`` holds them equal.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np


@dataclasses.dataclass
class Pop:
    name: str
    size: int
    is_input: bool
    alpha: float = 0.0
    v_th: float = 1.0
    rate: float = 0.0          # spikes per channel per step (inputs)
    #: neuron constants a configuration's generator and its reference
    #: share, beyond Eq. 1's ``alpha`` and ``v_th``
    params: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Proj:
    """One projection in CSR form: rows are source neurons."""

    name: str
    pre: str
    post: str
    n_source: int
    n_target: int
    indptr: np.ndarray         # (S + 1,) int64
    indices: np.ndarray        # (nnz,) int64 target columns
    values: np.ndarray         # (nnz,) float64 signed integer weights
    delays: np.ndarray         # (nnz,) int64 in [1, delay_range]
    delay_range: int

    @property
    def n_synapses(self) -> int:
        return int(self.indptr[-1])

    def sources(self) -> np.ndarray:
        return np.repeat(np.arange(self.n_source, dtype=np.int64),
                         np.diff(self.indptr))


@dataclasses.dataclass
class NetSpec:
    """A network as plain arrays: what both sides of the check are given.

    ``back_edges`` holds the indices of the projections that the program
    routes through its one-step feedback ring (``chipbench/graph.py``);
    a chain has none."""

    name: str
    pops: List[Pop]
    projs: List[Proj]
    back_edges: Tuple[int, ...] = ()

    @property
    def inputs(self) -> List[Pop]:
        return [p for p in self.pops if p.is_input]

    @property
    def n_input(self) -> int:
        return sum(p.size for p in self.inputs)

    @property
    def n_neurons(self) -> int:
        return sum(p.size for p in self.pops)

    @property
    def n_synapses(self) -> int:
        return sum(e.n_synapses for e in self.projs)

    def input_slices(self) -> Dict[str, Tuple[int, int]]:
        """Columns of the concatenated input train, in declared order."""
        out, off = {}, 0
        for p in self.inputs:
            out[p.name] = (off, off + p.size)
            off += p.size
        return out


# -- the sampler --------------------------------------------------------------

def _dense_draw(S, T, density, delay_range, inh, seed):
    """``random_layer`` with per-source delays, returned as CSR arrays."""
    rng = np.random.default_rng(seed)
    mask = rng.random((S, T)) < density
    mag = rng.integers(1, 128, size=(S, T)).astype(np.float64)
    sign = np.where(rng.random((S, T)) < inh, -1.0, 1.0)
    per_src = rng.integers(1, delay_range + 1, size=(S, 1))
    src, tgt = np.nonzero(mask)
    indptr = np.zeros(S + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=S), out=indptr[1:])
    return (indptr, tgt.astype(np.int64), (mag * sign)[src, tgt],
            per_src[src, 0].astype(np.int64))


def generate(cfg: dict) -> NetSpec:
    """The configuration's network, equal to the repository generator's at
    the configuration's ``structure_seed``."""
    net = cfg["network"]
    pops = []
    for p in net["populations"]:
        if p.get("input"):
            pops.append(Pop(p["name"], int(p["size"]), True,
                            rate=float(p["rate"])))
        else:
            pops.append(Pop(p["name"], int(p["size"]), False,
                            alpha=float(p["alpha"]), v_th=float(p["v_th"])))
    sizes = {p.name: p.size for p in pops}
    seed0 = int(net["structure_seed"])
    projs = []
    for k, e in enumerate(net["projections"]):
        S, T = sizes[e["pre"]], sizes[e["post"]]
        indptr, indices, values, delays = _dense_draw(
            S, T, float(e["density"]), int(e["delay_range"]),
            float(e["inhibitory_fraction"]), seed0 + k)
        projs.append(Proj(
            name=f"{e['pre']}->{e['post']}", pre=e["pre"], post=e["post"],
            n_source=S, n_target=T, indptr=indptr, indices=indices,
            values=values, delays=delays, delay_range=int(e["delay_range"])))
    return NetSpec(name=net["name"], pops=pops, projs=projs)


def to_program(spec: NetSpec):
    """The same network as the program's ``SNNNetwork``: a chain of
    layers, each projection onto the next population."""
    from repro.core.layer import LIFParams, SNNLayer, SNNNetwork

    pops = {p.name: p for p in spec.pops}
    layers = []
    for i, e in enumerate(spec.projs):
        w = np.zeros((e.n_source, e.n_target))
        d = np.ones((e.n_source, e.n_target), np.int64)
        src = e.sources()
        w[src, e.indices] = e.values
        d[src, e.indices] = e.delays
        post = pops[e.post]
        layers.append(SNNLayer(
            weights=w, delays=d, delay_range=e.delay_range,
            lif=LIFParams(alpha=post.alpha, v_th=post.v_th),
            name=f"{spec.name}.l{i}"))
    return SNNNetwork(layers=layers, name=spec.name)
