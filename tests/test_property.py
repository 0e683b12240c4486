"""Hypothesis property tests over the system's invariants."""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.core import (
    LayerCharacter,
    compile_parallel,
    compile_serial,
    random_layer,
    serial_pe_count,
)
from repro.core.cost_model import equal_parts
from repro.core.layer import LIFParams
from repro.core.runtime import run_parallel, run_reference, run_serial
from repro.core.serial_compiler import pack_rows, unpack_rows

SLOW = settings(
    max_examples=20, deadline=None,
    suppress_health_check=[HealthCheck.too_slow],
)


@given(n=st.integers(1, 5000), cap=st.integers(1, 500))
@settings(max_examples=200, deadline=None)
def test_equal_parts_invariants(n, cap):
    parts = equal_parts(n, cap)
    assert sum(parts) == n
    assert all(1 <= p <= cap for p in parts)
    assert max(parts) - min(parts) <= 1


@given(
    w=st.lists(st.integers(-127, 127).filter(lambda x: x != 0),
               min_size=1, max_size=64),
    dr=st.integers(1, 127),
)
@settings(max_examples=100, deadline=None)
def test_pack_unpack_roundtrip(w, dr):
    rng = np.random.default_rng(0)
    weights = np.asarray(w, np.float64)
    delays = rng.integers(0, dr + 1, len(w))
    idx = rng.integers(0, 2**16, len(w))
    packed = pack_rows(weights, delays, idx)
    w2, d2, i2 = unpack_rows(packed)
    np.testing.assert_array_equal(w2, weights)
    np.testing.assert_array_equal(d2, delays)
    np.testing.assert_array_equal(i2, idx)


@given(
    ns=st.integers(10, 500), nt=st.integers(10, 500),
    d1=st.floats(0.05, 0.5), bump=st.floats(0.05, 0.5),
    dr=st.integers(1, 16),
)
@settings(max_examples=60, deadline=None)
def test_serial_count_monotone_in_density(ns, nt, d1, bump, dr):
    c1 = serial_pe_count(LayerCharacter(ns, nt, d1, dr))
    c2 = serial_pe_count(LayerCharacter(ns, nt, min(1.0, d1 + bump), dr))
    assert c2 >= c1


@given(
    ns=st.integers(5, 80), nt=st.integers(5, 80),
    dens=st.floats(0.05, 1.0), dr=st.integers(1, 8),
    gran=st.sampled_from(["source", "synapse"]),
    seed=st.integers(0, 10_000),
)
@SLOW
def test_compilers_conserve_synapses(ns, nt, dens, dr, gran, seed):
    layer = random_layer(ns, nt, dens, dr, seed=seed, delay_granularity=gran)
    sp = compile_serial(layer)
    pp = compile_parallel(layer)
    n_serial = sum(c.synaptic_rows.size for c in sp.cells)
    n_parallel = sum(
        int((sl.matrix[: nt, : len(sl.col_sources)] != 0).sum())
        for sl in pp.slices
    )
    assert n_serial == layer.n_synapses
    assert n_parallel == layer.n_synapses
    assert sp.pe_count >= 1 and pp.pe_count >= 1


@given(
    ns=st.integers(8, 40), nt=st.integers(8, 40),
    dens=st.floats(0.1, 1.0), dr=st.integers(1, 4),
    gran=st.sampled_from(["source", "synapse"]),
    seed=st.integers(0, 1000),
)
@settings(max_examples=10, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
def test_runtime_equivalence_property(ns, nt, dens, dr, gran, seed):
    lif = LIFParams(alpha=0.5, v_th=64.0)
    layer = random_layer(ns, nt, dens, dr, seed=seed, delay_granularity=gran)
    layer.lif = lif
    rng = np.random.default_rng(seed)
    spikes = (rng.random((8, 1, ns)) < 0.3).astype(np.float32)
    z_ref = run_reference(layer, spikes, lif)
    np.testing.assert_array_equal(z_ref, run_serial(layer, spikes, lif))
    np.testing.assert_array_equal(z_ref, run_parallel(layer, spikes, lif))
