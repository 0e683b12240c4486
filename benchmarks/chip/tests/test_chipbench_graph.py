"""The shared graph module: CSR networks handed to the program as
``SparseProjection``s, and the event-driven reference against the
program's unrolled oracle and against the chain reference."""
import tracemalloc

import ml_dtypes
import numpy as np
import pytest

import faultkit
from chipbench import graph, netgen, reference, spec

GESTURE = spec.load_json(spec.BENCH_DIR / "configs" / "gesture.json")
RECURRENT = spec.load_json(faultkit.DATA / "recurrent.json")
WIDE = spec.load_json(faultkit.DATA / "recurrent_wide.json")


def generate(cfg):
    return spec.network_code(cfg, "generator").generate(cfg)


def spikes(s, steps, batch, seed, rate=None):
    """A (steps, batch, n_input) train, each input at ``rate`` or its own."""
    rng = np.random.default_rng(seed)
    x = np.zeros((steps, batch, s.n_input), np.uint8)
    for p in s.inputs:
        a, b = s.input_slices()[p.name]
        x[:, :, a:b] = rng.random((steps, batch, b - a)) < (rate or p.rate)
    return x


def test_a_configuration_names_its_network_code():
    assert spec.network_code(GESTURE, "generator") is netgen
    assert spec.network_code(GESTURE, "reference") is reference
    assert spec.network_code(RECURRENT, "reference") is graph
    gen = spec.network_code(RECURRENT, "generator")
    assert gen.generate.__code__.co_filename == str(
        faultkit.DATA / "recurrent_net.py")
    assert spec.network_code(RECURRENT, "generator") is gen   # loaded once
    with pytest.raises(ValueError):
        spec.load_module("../../BENCHMARK.json")
    with pytest.raises(FileNotFoundError):
        spec.load_module("chipbench/no_such_module.py")


def test_gesture_from_the_default_generator_equals_the_program_generator():
    from repro.core import feedforward_network

    gen = spec.network_code(GESTURE, "generator")
    s = gen.generate(GESTURE)
    assert s.back_edges == () and all(p.params == {} for p in s.pops)
    want = feedforward_network([2048, 20, 4], density=0.0316, delay_range=1,
                               seed=0, name="gesture")
    for got, ref in zip(gen.to_program(s).layers, want.layers):
        np.testing.assert_array_equal(got.weights, ref.weights)
        np.testing.assert_array_equal(got.delays, ref.delays)


def test_fixed_total_number_draws_distinct_sorted_pairs():
    indptr, indices = graph.fixed_total_number(
        50, 40, 700, np.random.default_rng(1))
    assert indptr[-1] == indices.size == 700
    src = np.repeat(np.arange(50), np.diff(indptr))
    flat = src * 40 + indices
    assert np.all(np.diff(flat) > 0) and indices.max() < 40
    with pytest.raises(ValueError):
        graph.fixed_total_number(3, 3, 10, np.random.default_rng(1))


def test_the_program_gets_the_declared_back_edges_and_csr_only():
    from repro.core.layer import is_sparse

    s = generate(RECURRENT)
    net = graph.to_graph_program(s)
    assert s.back_edges == (1, 3)                  # E -> E, I -> E
    assert net.back_edges == frozenset(s.back_edges)
    assert all(is_sparse(e) for e in net.projections)
    assert [p.name for p in net.input_populations] == ["x1", "x2"]
    # left undeclared, the program would add back-edges of its own
    s.back_edges = ()
    with pytest.raises(ValueError, match="back-edges"):
        graph.to_graph_program(s)
    with pytest.raises(ValueError, match="cycle"):
        graph.simulate_graph(s, spikes(s, 4, 1, 0))


@pytest.mark.parametrize("steps,batch,seed,rate", [
    (48, 3, 1, None), (64, 2, 2, 0.35), (32, 8, 3, 0.05)])
def test_simulate_graph_equals_the_unrolled_oracle(steps, batch, seed, rate):
    from repro.core.runtime import run_graph_reference

    s = generate(RECURRENT)
    x = spikes(s, steps, batch, seed, rate)
    got = graph.simulate_graph(s, x)
    want = run_graph_reference(graph.to_graph_program(s), x.astype(np.float32))
    for e, z in zip(s.projs, want):
        np.testing.assert_array_equal(got[e.post], z)
    assert all(t.any() for t in got.values())


@pytest.mark.parametrize("dtype", [np.float32, ml_dtypes.bfloat16])
def test_simulate_graph_equals_the_chain_reference_on_gesture(dtype):
    s = generate(GESTURE)
    x = spikes(s, 40, 3, 4)
    got = graph.simulate_graph(s, x, dtype=dtype)
    want = reference.simulate(s, x, dtype=dtype)
    assert set(got) == set(want) == {"hidden", "output"}
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])


def test_work_per_step_follows_the_rows_of_the_sources_that_fired(monkeypatch):
    s = generate(RECURRENT)
    routed, rows = [], graph.rows

    def counted(indptr, sources):
        out = rows(indptr, sources)
        routed.append(out.size)
        return out

    monkeypatch.setattr(graph, "rows", counted)
    x = spikes(s, 32, 2, 6)
    trains = graph.simulate_graph(s, x)
    fired = {p: x[:, :, a:b] for p, (a, b) in s.input_slices().items()}
    fired.update(trains)
    want = sum(int((fired[e.pre].astype(np.int64) @ np.diff(e.indptr)).sum())
               for e in s.projs)
    assert sum(routed) == want
    assert want < s.n_synapses * 32 * 2
    routed.clear()
    graph.simulate_graph(s, np.zeros_like(x))      # nothing fires
    assert sum(routed) == 0


def test_the_wide_fixture_stays_csr():
    """Over 2**24 dense elements at density 0.002: the spec and the program
    hold the synapses alone, never an (S, T) array of even one byte an
    element."""
    from repro.core.layer import DENSE_ELEMENT_CAP, is_sparse

    tracemalloc.start()
    try:
        s = generate(WIDE)
        net = graph.to_graph_program(s)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    wide = max(s.projs, key=lambda e: e.n_source * e.n_target)
    assert (wide.n_source, wide.n_target) == (8192, 4096)
    assert wide.n_source * wide.n_target > DENSE_ELEMENT_CAP
    assert wide.n_synapses / (wide.n_source * wide.n_target) == pytest.approx(
        0.002, rel=0.01)
    assert all(is_sparse(e) for e in net.projections)
    assert peak < wide.n_source * wide.n_target
