"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.lif_update import lif_update, lif_update_ref
from repro.kernels.spike_wdm_matmul import spike_wdm_matmul, spike_wdm_matmul_ref
from repro.kernels.spike_wdm_matmul.kernel import (
    BM_MAX,
    VMEM_BUDGET,
    vmem_bytes,
    wdm_tiles,
)

RNG = np.random.default_rng(0)


def rand_wdm(m, k):
    return jnp.asarray(RNG.integers(-128, 128, (m, k)), jnp.int8)


def rand_spikes(k, n, p=0.3):
    return jnp.asarray(RNG.random((k, n)) < p, jnp.int8)


class TestSpikeWDMMatmul:
    @pytest.mark.parametrize("m,k,n", [
        (4, 16, 1),          # one SpiNNaker2 MAC tile
        (128, 128, 128),     # one MXU tile
        (128, 512, 128),     # K-loop accumulation
        (300, 700, 36),      # unaligned: one ragged column block
        (1, 1, 1),           # degenerate
        (257, 1025, 129),    # prime-ish off-by-one
        # ragged row and column blocks: bm 1056, bk 1024 (bn 256 at 129)
        (2100, 1000, 1),
        (2100, 1000, 8),
        (2100, 1000, 129),
        (1065, 3841, 1),     # one column past a 3840-column block
        (1, 700, 8),         # a map with one row
    ])
    def test_matches_ref(self, m, k, n):
        a, x = rand_wdm(m, k), rand_spikes(k, n)
        out = spike_wdm_matmul(a, x, interpret=True)
        ref = spike_wdm_matmul_ref(a, x)
        assert out.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_dense_spike_values(self):
        """int8 x int8 accumulation must not saturate (int32 out)."""
        a = jnp.full((128, 512), 127, jnp.int8)
        x = jnp.ones((512, 8), jnp.int8)
        out = spike_wdm_matmul(a, x, interpret=True)
        assert int(out[0, 0]) == 127 * 512

    def test_negative_weights(self):
        a = jnp.full((4, 16), -128, jnp.int8)
        x = jnp.ones((16, 2), jnp.int8)
        out = spike_wdm_matmul(a, x, interpret=True)
        assert int(out[0, 0]) == -128 * 16

    def test_zero_columns(self):
        a = rand_wdm(32, 0)
        x = rand_spikes(0, 4)
        out = spike_wdm_matmul(a, x, interpret=True)
        assert out.shape == (32, 4) and int(jnp.abs(out).sum()) == 0

    def test_rejects_non_int8(self):
        with pytest.raises(TypeError):
            spike_wdm_matmul_ref(
                jnp.ones((4, 4), jnp.float32), jnp.ones((4, 4), jnp.int8)
            )

    @pytest.mark.parametrize("m,k", [
        (14395, 32744), (2948, 131316), (5479, 30681), (1065, 122480),
        (1065, 111189), (2948, 22021), (2948, 7633),
    ])
    def test_tiles_fit_vmem(self, m, k):
        """The microcircuit's 7 parallel maps at batch 1: the rule's blocks
        fit its VMEM budget, align to the int8 (32, 128) tile, hold >= 2 MiB
        of weights, and re-read the spike block for <= 1/8 of the map."""
        bm, bk, bn = wdm_tiles(m, k, 1)
        assert vmem_bytes(bm, bk, bn) <= VMEM_BUDGET
        assert (bm == m <= BM_MAX or bm % 32 == 0) and bk % 128 == 0
        assert bm * bk >= 2 << 20 and bn == 128
        row_blocks, cols = -(-m // bm), -(-k // bk) * bk
        assert row_blocks * cols * bn <= m * k / 8

    @pytest.mark.parametrize("m,k,tiles", [
        (14395, 32744, (1824, 2048, 128)),    # TH -> L6E
        (2948, 131316, (1504, 2688, 128)),    # L5E -> L6I
        (5479, 30681, (1856, 2048, 128)),     # TH -> L4I
        (1065, 122480, (1065, 3840, 128)),    # L5E -> L5I
        (1065, 111189, (1065, 3840, 128)),    # L6E -> L5I
        (2948, 22021, (1504, 2560, 128)),     # TH -> L6I
        (2948, 7633, (1504, 2560, 128)),      # L4I -> L6I
    ])
    def test_microcircuit_tiles(self, m, k, tiles):
        """The blocks the microcircuit's 7 parallel maps run with at
        batch 1 (timed on the chip with exactly these tiles)."""
        assert wdm_tiles(m, k, 1) == tiles

    def test_gesture_parallel_layer_is_one_block(self):
        """The gesture network's parallel layer (2048 -> 20, delay 1) at
        batch 8: the whole map and every spike lane in one block."""
        from repro.core import SwitchingCompiler, feedforward_network
        from repro.core.runtime.executor import get_layer_executable

        layer = feedforward_network([2048, 20, 4], 0.0316, 1).layers[0]
        exe = get_layer_executable(
            SwitchingCompiler("parallel").compile_layer(layer), layer.lif
        )
        m, k = exe.wdm_stack.shape
        assert (m, k) == (20, 965)
        assert wdm_tiles(m, k, 8) == (20, 1024, 128)

    @pytest.mark.parametrize("bm,bn,bk", [(128, 128, 128), (128, 128, 512)])
    def test_block_shapes(self, bm, bn, bk):
        a, x = rand_wdm(256, 1024), rand_spikes(1024, 256)
        out = spike_wdm_matmul(a, x, bm=bm, bn=bn, bk=bk, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(spike_wdm_matmul_ref(a, x))
        )


class TestLIFUpdate:
    @pytest.mark.parametrize("n,b", [(256, 128), (300, 36), (1, 1), (1000, 3)])
    @pytest.mark.parametrize("alpha,v_th", [(0.5, 64.0), (0.9, 1.0)])
    def test_matches_ref(self, n, b, alpha, v_th):
        i = jnp.asarray(RNG.normal(size=(n, b)) * 10, jnp.float32)
        v = jnp.asarray(RNG.normal(size=(n, b)), jnp.float32)
        z = jnp.asarray(RNG.integers(0, 2, (n, b)), jnp.float32)
        vn, zn = lif_update(i, v, z, alpha=alpha, v_th=v_th, interpret=True)
        vr, zr = lif_update_ref(i, v, z, alpha=alpha, v_th=v_th)
        np.testing.assert_allclose(np.asarray(vn), np.asarray(vr), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(zn), np.asarray(zr))

    def test_threshold_fire_and_reset_semantics(self):
        # V' = I + alpha*V - z*V_th ; z' = V' >= V_th
        i = jnp.asarray([[100.0], [0.0]], jnp.float32)
        v = jnp.asarray([[0.0], [128.0]], jnp.float32)
        z = jnp.asarray([[0.0], [1.0]], jnp.float32)
        vn, zn = lif_update(i, v, z, alpha=0.5, v_th=64.0, interpret=True)
        assert float(vn[0, 0]) == 100.0 and float(zn[0, 0]) == 1.0
        assert float(vn[1, 0]) == 0.0 and float(zn[1, 0]) == 0.0


class TestDispatch:
    """One rule routes every wrapper: the backend alone decides, and a
    kernel that cannot compile fails the call instead of falling back."""

    @pytest.mark.parametrize("interpret,want", [(None, True), (False, False),
                                                (True, False)])
    def test_use_reference_off_tpu(self, interpret, want):
        from repro.kernels.dispatch import use_reference

        assert use_reference(interpret) is want

    @pytest.mark.parametrize("call", ["lif_update", "spike_wdm_matmul",
                                      "lif_parallel_scan"])
    def test_compiled_kernel_off_tpu_raises(self, call):
        from repro.kernels.lif_parallel_scan import lif_parallel_scan

        x = jnp.zeros((8, 1), jnp.float32)
        run = {
            "lif_update": lambda: lif_update(
                x, x, x, alpha=0.5, v_th=64.0, interpret=False),
            "spike_wdm_matmul": lambda: spike_wdm_matmul(
                rand_wdm(4, 8), rand_spikes(8, 1), interpret=False),
            "lif_parallel_scan": lambda: lif_parallel_scan(
                x, alpha=0.5, interpret=False),
        }[call]
        with pytest.raises(ValueError, match="interpret mode"):
            run()
