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
from repro.optim.compression import dequantize, quantize

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


@given(st.lists(st.floats(-1e4, 1e4, allow_nan=False), min_size=1, max_size=256))
@settings(max_examples=100, deadline=None)
def test_int8_compression_error_bound(xs):
    import jax.numpy as jnp
    g = jnp.asarray(np.asarray(xs, np.float32))
    c = quantize(g)
    err = np.abs(np.asarray(dequantize(c) - g))
    amax = float(np.max(np.abs(np.asarray(g))))
    assert err.max() <= amax / 127.0 * 0.5 + 1e-6


def test_attention_causality():
    """Perturbing future tokens must not change past logits (all archs with
    attention; exercises the Q-block streaming mask)."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs import smoke_config
    from repro.models import init as minit, model as M

    for arch in ("qwen3-8b", "recurrentgemma-2b", "mamba2-130m"):
        cfg = smoke_config(arch)
        if cfg.moe is not None:
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
        params = minit.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        toks = rng.integers(0, cfg.vocab, (1, 16))
        toks2 = toks.copy()
        toks2[:, 10:] = rng.integers(0, cfg.vocab, (1, 6))
        l1, _ = M.prefill(params, cfg, {"tokens": jnp.asarray(toks, jnp.int32)}, 16)
        # compare hidden logits at position 9 via full forward
        def logits_at(t):
            batch = {"tokens": jnp.asarray(t[:, :10], jnp.int32)}
            l, _ = M.prefill(params, cfg, batch, 16)
            return np.asarray(l)
        np.testing.assert_allclose(logits_at(toks), logits_at(toks2),
                                   rtol=1e-5, err_msg=arch)


def test_local_attention_window_equivalence():
    """With window >= seq_len, windowed attention == full attention."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs import smoke_config
    from repro.models import init as minit, model as M

    cfg = smoke_config("qwen3-8b")
    params = minit.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 12)), jnp.int32)}
    full = M.train_loss(params, cfg, batch)
    windowed = M.train_loss(
        params, dataclasses.replace(cfg, attn_window=64), batch)
    np.testing.assert_allclose(float(full), float(windowed), rtol=1e-6)
