"""The benchmark's networks and its plain CSR reference, against the
program's generators and its unrolled oracle at small sizes."""
import ml_dtypes
import numpy as np
import pytest

from chipbench import netgen, reference, simulate, spec

GESTURE = spec.load_json(spec.BENCH_DIR / "configs" / "gesture.json")


def spikes(spec_, steps, batch, rate, seed):
    rng = np.random.default_rng(seed)
    return (rng.random((steps, batch, spec_.n_input)) < rate).astype(np.uint8)


def test_gesture_equals_the_program_generator():
    from repro.core import feedforward_network

    net = netgen.to_program(netgen.generate(GESTURE))
    want = feedforward_network([2048, 20, 4], density=0.0316, delay_range=1,
                               seed=0, name="gesture")
    for got, ref in zip(net.layers, want.layers):
        np.testing.assert_array_equal(got.weights, ref.weights)
        np.testing.assert_array_equal(got.delays, ref.delays)
        assert (got.lif.alpha, got.lif.v_th) == (0.5, 64.0)


def test_seed_draws_the_inputs_and_not_the_network():
    a, b = netgen.generate(GESTURE), netgen.generate(GESTURE)
    for x, y in zip(a.projs, b.projs):
        np.testing.assert_array_equal(x.values, y.values)
    traffic = spec.load_json(spec.BENCH_DIR / "traffic" / "window_t256.json")
    one = simulate.poisson_inputs(a, traffic, 1, count=1)[0]
    assert np.array_equal(one, simulate.poisson_inputs(a, traffic, 1, count=1)[0])
    assert not np.array_equal(
        one, simulate.poisson_inputs(a, traffic, 2**40 + 3, count=1)[0])


@pytest.mark.parametrize("steps,batch,rate", [(24, 3, 0.15), (40, 1, 0.3),
                                               (16, 8, 0.05)])
def test_reference_equals_the_unrolled_oracle(steps, batch, rate):
    from repro.core.runtime import run_graph_reference

    s = netgen.generate(GESTURE)
    net = netgen.to_program(s)
    x = spikes(s, steps=steps, batch=batch, rate=rate, seed=5)
    got = reference.simulate(s, x)
    want = run_graph_reference(net, x.astype(np.float32))
    for e, z in zip(s.projs, want):
        np.testing.assert_array_equal(got[e.post], z)
    assert sum(int(t.sum()) for t in got.values()) > 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bf16_control_differs_from_the_reference(seed):
    """Over a run's inputs, the control flips spikes on every seed."""
    s = netgen.generate(GESTURE)
    traffic = spec.load_json(spec.BENCH_DIR / "traffic" / "window_t256.json")
    flips = 0
    for x in simulate.poisson_inputs(s, traffic, seed):
        f32 = reference.simulate(s, x)
        bf16 = reference.simulate(s, x, dtype=ml_dtypes.bfloat16)
        flips += sum(int(np.count_nonzero(f32[k] != bf16[k])) for k in f32)
    assert flips > 0
