"""Densify-and-diff harness: CSR storage vs the dense oracle, every path.

:class:`~repro.core.layer.SparseProjection` promises to be *storage*, not
*semantics*: a CSR net must spike bit-identically to the dense net its
``densify()`` produces, on every launch path the executor offers —

* **solo**    — each request alone through the fused scan (batch 1);
* **fused**   — the in-scan batched path with ``valid_steps`` masking;
* **vmap**    — ``jax.vmap`` over the request axis;
* **event / sparse / dense** — the fused path with every serial layer
  forced onto one kernel form (the ELL gather is the sparse-native one;
  event and dense must agree with it bit-for-bit);
* **sharded** — the fused path after ``shard()`` (identity on 1 device).

Ground truth is the brute-force unrolled numpy oracle
(:func:`run_graph_reference`), which densifies internally — so every
sparse path is diffed against exactly the arithmetic its densified twin
performs.  All weights are int8-magnitude integers: accumulation is
exact in float32 and the assertions are **bit-identical**, no atol.
"""
import numpy as np
import pytest

from repro.core import Population, SwitchingCompiler, random_layer
from repro.core.layer import (
    DENSE_ELEMENT_CAP,
    DenseStorageError,
    LIFParams,
    SNNNetwork,
    SparseProjection,
    is_sparse,
    random_projection,
    random_sparse_projection,
)
from repro.core.runtime import network_executable, run_graph_reference
from repro.core.switching import CompileReport

LIF = LIFParams(alpha=0.5, v_th=64.0)

#: Paradigm mixes under test (chains through the graph API — CSR
#: projections carry explicit pre/post).  Seeds are fixed literals so a
#: failing geometry reproduces run-to-run.
MIXES = {
    "serial-only": (["serial", "serial"], 111),
    "serial-sandwich": (["serial", "parallel", "serial"], 222),
    "parallel-first": (["parallel", "serial"], 333),
}

#: Recurrent geometries: (populations, projection specs, paradigms, seed).
#: Projection spec: (pre, post, density, delay_range).
GRAPHS = {
    "self-loop": (
        [("in", 14), ("h", 18), ("out", 9)],
        [("in", "h", 0.3, 2), ("h", "h", 0.25, 3), ("h", "out", 0.4, 2)],
        ["serial", "parallel", "serial"],
        909,
    ),
    "skip-and-loop": (
        [("in", 15), ("h1", 14), ("h2", 12), ("out", 7)],
        [("in", "h1", 0.3, 2), ("h1", "h2", 0.35, 2), ("in", "h2", 0.25, 1),
         ("h2", "h2", 0.3, 2), ("h2", "out", 0.4, 2), ("out", "h1", 0.3, 1)],
        ["serial", "parallel", "serial", "serial", "parallel", "serial"],
        919,
    ),
}

PATHS = ["fused", "vmap", "event", "sparse", "dense", "sharded", "solo"]

_CACHE = {}


def _compile(net, paradigms):
    return CompileReport(layers=[
        SwitchingCompiler(p).compile_layer(l)
        for p, l in zip(paradigms, net.layers)
    ])


def _fixture(kind, name):
    """(sparse net, report, exe, densified-twin exe, spikes, valid, want)."""
    key = (kind, name)
    if key in _CACHE:
        return _CACHE[key]
    if kind == "mix":
        paradigms, seed = MIXES[name]
        rng = np.random.default_rng(seed)
        sizes = [int(rng.integers(12, 28)) for _ in range(len(paradigms) + 1)]
        pops = [Population(f"{name}.p{i}", s) for i, s in enumerate(sizes)]
        spec = [
            (pops[i], pops[i + 1],
             float(rng.uniform(0.15, 0.5)), int(rng.integers(1, 7)))
            for i in range(len(paradigms))
        ]
    else:
        pop_spec, proj_spec, paradigms, seed = GRAPHS[name]
        rng = np.random.default_rng(seed)
        pops = [Population(n, s) for n, s in pop_spec]
        by_name = {p.name: p for p in pops}
        spec = [
            (by_name[pre], by_name[post], density, dr)
            for pre, post, density, dr in proj_spec
        ]
    projs = []
    for pre, post, density, dr in spec:
        p = random_sparse_projection(
            pre, post, density, dr,
            seed=int(rng.integers(0, 2**31)),
            delay_granularity=rng.choice(["source", "synapse"]),
        )
        p.lif = LIF
        projs.append(p)
    net = SNNNetwork(populations=pops, projections=projs, name=name)
    assert all(is_sparse(e) for e in net.projections)
    report = _compile(net, paradigms)
    exe = network_executable(net, report)
    # the densified twin: same weights, dense storage, same paradigms
    dnet = SNNNetwork(
        populations=pops, projections=[e.densify() for e in projs],
        name=f"{name}.densified",
    )
    dexe = network_executable(dnet, _compile(dnet, paradigms))
    batch = 4
    spikes = (rng.random((12, batch, net.n_input)) < 0.3).astype(np.float32)
    valid = np.asarray(
        [12, int(rng.integers(1, 12)), int(rng.integers(1, 12)), 0],
        np.int32,
    )
    want = _solo_oracle(net, spikes, valid)
    _CACHE[key] = (net, report, exe, dexe, spikes, valid, want)
    return _CACHE[key]


def _solo_oracle(net, spikes, valid):
    """Each live request alone through the unrolled numpy oracle (which
    densifies internally), trimmed to its true length."""
    outs = [
        np.zeros(spikes.shape[:2] + (l.n_target,), np.float32)
        for l in net.layers
    ]
    for b in range(spikes.shape[1]):
        n = int(valid[b])
        if n == 0:
            continue
        solo = run_graph_reference(net, spikes[:n, b : b + 1])
        for dst, z in zip(outs, solo):
            dst[:n, b] = z[:, 0]
    return outs


def _launch(exe, path, spikes, valid):
    if path == "fused":
        return exe.run(spikes, valid_steps=valid)
    if path == "vmap":
        return exe.run(spikes, valid_steps=valid, batched=True)
    if path in ("event", "sparse", "dense"):
        return exe.run(spikes, valid_steps=valid, serial_form=path)
    if path == "sharded":
        exe.shard()                       # identity fallback on 1 device
        return exe.run(spikes, valid_steps=valid)
    if path == "solo":
        return [
            np.concatenate(
                [exe.run(spikes[:, b : b + 1])[i]
                 for b in range(spikes.shape[1])],
                axis=1,
            )
            for i in range(len(exe.metas))
        ]
    raise AssertionError(path)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("mix", sorted(MIXES))
def test_sparse_chain_equals_densified_oracle(mix, path):
    """Every (paradigm mix x launch path) on CSR storage is bit-identical
    to the densified oracle, masked slots included."""
    net, report, exe, dexe, spikes, valid, want = _fixture("mix", mix)
    if path == "solo":
        got = _launch(exe, "solo", spikes, None)
        full = run_graph_reference(net, spikes)
        for a, b in zip(got, full):
            np.testing.assert_array_equal(a, b)
        return
    got = _launch(exe, path, spikes, valid)
    assert len(got) == len(net.layers)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_sparse_recurrent_equals_densified_oracle(graph, path):
    """Recurrent CSR geometries (self-loops, skip connections, back-edges)
    match the unrolled oracle bit-for-bit on every path."""
    net, report, exe, dexe, spikes, valid, want = _fixture("graph", graph)
    if path == "solo":
        got = _launch(exe, "solo", spikes, None)
        full = run_graph_reference(net, spikes)
        for a, b in zip(got, full):
            np.testing.assert_array_equal(a, b)
        return
    got = _launch(exe, path, spikes, valid)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("name", sorted(MIXES) + sorted(GRAPHS))
def test_sparse_exe_equals_densified_twin_exe(name):
    """The CSR executable and the executable compiled from its densified
    twin agree bit-for-bit — storage never leaks into semantics."""
    kind = "mix" if name in MIXES else "graph"
    net, report, exe, dexe, spikes, valid, _ = _fixture(kind, name)
    a = exe.run(spikes, valid_steps=valid)
    b = dexe.run(spikes, valid_steps=valid)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_round_trip_is_exact():
    """densify(from_dense(W)) == W elementwise, connected delays included."""
    layer = random_layer(23, 17, density=0.3, delay_range=5, seed=42,
                         delay_granularity="synapse")
    sp = SparseProjection.from_dense(layer, pre="a", post="b")
    back = sp.densify()
    np.testing.assert_array_equal(back.weights, layer.weights)
    mask = layer.connectivity()
    np.testing.assert_array_equal(back.delays[mask], layer.delays[mask])
    assert (back.delays[~mask] == 1).all()     # canonical ignored slots


def test_forms_recorded_follow_choice_across_density_sweep():
    """At fixed batch, the recorded serial form moves monotonically toward
    dense as density grows, and always matches ``choose_form``."""
    batch = 8
    pops = None
    dense_flags = []
    for density in (0.002, 0.05, 0.6):
        a, b = Population(f"d{density}.a", 40), Population(f"d{density}.b", 40)
        proj = random_sparse_projection(a, b, density, 2, seed=13)
        proj.lif = LIF
        net = SNNNetwork(populations=[a, b], projections=[proj])
        report = _compile(net, ["serial"])
        exe = network_executable(net, report)
        sp = (np.random.default_rng(13).random((6, batch, 40)) < 0.3
              ).astype(np.float32)
        exe.run(sp)
        m = exe.metas[0]
        want = exe.cost_model.choose_form(
            m.n_rows, m.n_source, m.n_target, m.delay_range, batch
        )
        assert report.serial_forms[("fused", batch)] == (want,)
        dense_flags.append(want == "dense")
    assert dense_flags == sorted(dense_flags)  # toward dense, never back


# -- dense-storage budget ------------------------------------------------------


def test_dense_cap_rejects_oversized_generators():
    """Dense generators refuse to materialize past the element cap, and
    the error tells you the way out (sparse storage)."""
    with pytest.raises(DenseStorageError, match="sparse storage"):
        random_layer(5000, 5000, density=0.001, delay_range=2, seed=0)
    a, b = Population("big.a", 5000), Population("big.b", 5000)
    with pytest.raises(DenseStorageError, match="sparse storage"):
        random_projection(a, b, 0.001, 2, seed=0)
    with pytest.raises(DenseStorageError, match="max_elements"):
        random_layer(5000, 5000, density=0.001, delay_range=2, seed=0)
    # the cap is a default, not a wall: callers may raise it explicitly
    assert 5000 * 5000 > DENSE_ELEMENT_CAP
    layer = random_layer(5000, 5000, density=0.0001, delay_range=2, seed=0,
                         max_elements=5000 * 5000)
    assert layer.n_source == 5000


def test_dense_cap_rejects_oversized_densify():
    a = Population("cap.a", 6000)
    b = Population("cap.b", 6000)
    sp = random_sparse_projection(a, b, 0.0005, 2, seed=1)
    with pytest.raises(DenseStorageError, match="sparse storage"):
        sp.densify()
    assert sp.densify(max_elements=6000 * 6000).n_source == 6000


# -- SpiNNCer-scale smoke: >=20k neurons through the fused scan ----------------


def test_20k_neuron_sparse_net_runs_fused_e2e():
    """A 20k-neuron, <=0.5%-dense recurrent net runs end-to-end through
    the fused scan in CSR storage — its dense (d_slots, S, T) operand
    (1.2e9 elements) is over the cap, so dense is not a lawful form.
    CI-sized: ~220k synapses, 4 timesteps, batch 4."""
    rng = np.random.default_rng(77)
    pin = Population("spin.in", 64)
    h = Population("spin.h", 20_000)
    out = Population("spin.out", 32)
    p_in = random_sparse_projection(pin, h, 0.08, 2, seed=771)
    p_rec = random_sparse_projection(h, h, 0.0004, 2, seed=772)
    p_out = random_sparse_projection(h, out, 0.05, 2, seed=773)
    for p in (p_in, p_rec, p_out):
        p.lif = LIF
    assert p_rec.density() <= 0.005
    net = SNNNetwork(populations=[pin, h, out],
                     projections=[p_in, p_rec, p_out])
    report = _compile(net, ["serial", "serial", "serial"])
    exe = network_executable(net, report)
    m = exe.metas[1]
    assert not exe.cost_model.dense_fits(m.n_source, m.n_target, m.delay_range)
    batch = 4
    spikes = (rng.random((4, batch, 64)) < 0.5).astype(np.float32)
    outs = exe.run(spikes)
    # auto picked the spike-driven form for the 20k recurrent edge (dense
    # can't exist; event and sparse touch all 160k rows every step, the
    # spike-driven form only the rows of the sources that fired) — and
    # the run is observably recorded
    assert report.serial_forms[("fused", batch)][1] == "spike"
    assert exe.cost_model.choose_form(
        m.n_rows, m.n_source, m.n_target, m.delay_range, batch,
        spike_ok=True) == "spike"
    assert outs[1].shape == (4, batch, 20_000)
    assert np.isfinite(outs[2]).all()
    # the event and sparse forms are the independent cross-checks at this
    # scale (the numpy oracle would densify 20k^2 — exactly what the cap
    # forbids)
    for form in ("event", "sparse"):
        other = exe.run(spikes, serial_form=form)
        for a, b in zip(outs, other):
            np.testing.assert_array_equal(a, b)
    # forcing the unlawful dense form is an explicit, hinted error
    with pytest.raises(ValueError, match="sparse"):
        exe.run(spikes, serial_form="dense")
