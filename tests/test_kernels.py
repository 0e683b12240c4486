"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.lif_update import lif_update, lif_update_ref
from repro.kernels.spike_wdm_matmul import spike_wdm_matmul, spike_wdm_matmul_ref

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
        (300, 700, 36),      # unaligned (padding path)
        (1, 1, 1),           # degenerate
        (257, 1025, 129),    # prime-ish off-by-one
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


class TestSSDChunk:
    """Mamba-2 SSD intra-chunk kernel vs pure-jnp oracle."""

    @pytest.mark.parametrize("q,h,p,n", [
        (256, 24, 64, 128),   # mamba2-130m production chunk
        (64, 3, 16, 32),      # small odd-ish
        (16, 1, 8, 8),        # tiny
        (128, 5, 32, 64),
    ])
    def test_matches_ref(self, q, h, p, n):
        from repro.kernels.ssd_chunk import ssd_chunk, ssd_chunk_ref
        x = jnp.asarray(RNG.normal(size=(q, h, p)), jnp.float32)
        b = jnp.asarray(RNG.normal(size=(q, h, n)), jnp.float32)
        c = jnp.asarray(RNG.normal(size=(q, h, n)), jnp.float32)
        la = jnp.asarray(-np.abs(RNG.normal(size=(q, h)) * 0.1), jnp.float32)
        y, s = ssd_chunk(x, b, c, la, interpret=True)
        yr, sr = ssd_chunk_ref(x, b, c, la)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                                   rtol=1e-4, atol=1e-4)

    def test_matches_model_ssd_math(self):
        """The kernel's chunk semantics == the mamba2 block's inline SSD
        (sequential-scan cross-check on a single chunk)."""
        from repro.kernels.ssd_chunk import ssd_chunk_ref
        q, h, p, n = 12, 2, 4, 6
        x = np.asarray(RNG.normal(size=(q, h, p)), np.float32)
        b = np.asarray(RNG.normal(size=(q, h, n)), np.float32)
        c = np.asarray(RNG.normal(size=(q, h, n)), np.float32)
        la = -np.abs(np.asarray(RNG.normal(size=(q, h)), np.float32) * 0.1)
        # sequential recurrence oracle: s_t = exp(la_t) s_{t-1} + b_t x_t^T
        y_seq = np.zeros((q, h, p), np.float32)
        s = np.zeros((h, n, p), np.float32)
        for t in range(q):
            for hh in range(h):
                s[hh] = np.exp(la[t, hh]) * s[hh] + np.outer(b[t, hh], x[t, hh])
                y_seq[t, hh] = c[t, hh] @ s[hh]
        y, state = ssd_chunk_ref(jnp.asarray(x), jnp.asarray(b),
                                 jnp.asarray(c), jnp.asarray(la))
        np.testing.assert_allclose(np.asarray(y), y_seq, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(state), s, rtol=1e-4, atol=1e-4)


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
