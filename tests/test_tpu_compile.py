"""Ahead-of-time compiles of the SNN path's TPU kernels for a v5e.

Each test lowers a Pallas kernel (or a whole fused gesture scan) with
``interpret=False`` for one chip of a described ``v5e:2x2`` topology and
compiles it with the TPU compiler installed here.  No chip is needed:
what the kernel compiler refuses (a gather it cannot lower, a block
over the VMEM budget) fails here instead of on the device.  The
topology is described inside a module-scoped fixture, never at import,
so every test worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import SwitchingCompiler, feedforward_network
from repro.core.layer import LIFParams
from repro.core.runtime import NetworkExecutable
from repro.core.runtime.serial_runtime import serial_project_sparse
from repro.kernels.lif_parallel_scan import lif_parallel_scan
from repro.kernels.lif_update import lif_update
from repro.kernels.spike_wdm_matmul import spike_wdm_matmul


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(one_chip):
    """An argument shape placed on the described chip."""
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip
    )


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.fixture(scope="module")
def gesture():
    """The source paper's 2048-20-4 gesture network (§IV-C)."""
    net = feedforward_network(
        [2048, 20, 4], density=0.0316, delay_range=1, seed=0, name="gesture"
    )
    for layer in net.layers:
        layer.lif = LIFParams(alpha=0.5, v_th=64.0)
    return net


def _lif(i, v, z):
    return lif_update(i, v, z, alpha=0.5, v_th=64.0, interpret=False)


@pytest.mark.parametrize("shape", [(20, 8), (80_000, 1), (80_000, 8)])
def test_lif_update_compiles(spec, shape):
    """(80000, B) is the 100k scaffold's granule population: the wrapper
    pads it to block multiples instead of one whole-population block."""
    text = _compiled_text(_lif, spec(shape), spec(shape), spec(shape))
    assert "tpu_custom_call" in text


def test_lif_update_vmapped_compiles(spec):
    """The vmapped serving path: 8 request lanes of a (20, 1) update."""
    lane = spec((8, 20, 1))
    assert "tpu_custom_call" in _compiled_text(jax.vmap(_lif), lane, lane, lane)


@pytest.mark.parametrize("vmapped", [False, True])
def test_spike_wdm_matmul_compiles(spec, gesture, vmapped):
    """The gesture's parallel layers: WDM (20, C) and (4, C') int8."""
    report = SwitchingCompiler("parallel").compile_network(gesture)
    exe = NetworkExecutable.build(gesture, report)
    fn = lambda a, x: spike_wdm_matmul(a, x, interpret=False)
    if vmapped:
        fn = jax.vmap(fn, in_axes=(None, 0))
    for wdm, _, _ in exe.params:
        m, k = wdm.shape
        x = spec((8, k, 1) if vmapped else (k, 8), jnp.int8)
        assert "tpu_custom_call" in _compiled_text(
            fn, spec((m, k), jnp.int8), x
        )


@pytest.mark.parametrize("ell_shape,n_source", [
    ((40, 78), 2048),          # gesture layer 1, serial, micro-batch 8
    ((2048, 100), 80_000),     # a scaffold-scale source population
])
def test_sparse_form_compiles(spec, ell_shape, n_source):
    """The sparse form is one XLA gather; it compiles at 10^5 sources."""
    n_target = ell_shape[0] // 2          # delay_range 1: two ring slots

    def fn(val, idx, ring, x):
        return serial_project_sparse(
            val, idx, ring, x, jnp.int32(3),
            delay_range=1, n_target=n_target,
        )

    text = _compiled_text(
        fn, spec(ell_shape), spec(ell_shape, jnp.int32),
        spec((2, 8, n_target)), spec((8, n_source)),
    )
    assert "gather" in text and "tpu_custom_call" not in text


@pytest.mark.parametrize("feat", [160, 512])
def test_lif_parallel_scan_compiles(spec, feat):
    """T=256: two 128-step chunks; 160 = the gesture hidden layer x 8."""
    fn = lambda c: lif_parallel_scan(c, alpha=0.5, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, spec((256, feat)))


@pytest.mark.parametrize("policy", ["serial", "parallel"])
@pytest.mark.parametrize("path", ["fused", "vmap"])
def test_gesture_scan_compiles(one_chip, gesture, policy, path):
    """The whole served gesture scan at micro-batch 8, as a launch runs it."""
    report = SwitchingCompiler(policy).compile_network(gesture)
    exe = NetworkExecutable.build(gesture, report)
    compiled = exe.lower(
        32, 8, path=path, interpret=False, sharding=one_chip
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
