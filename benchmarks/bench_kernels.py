"""Pallas kernel micro-bench (interpret mode on CPU; TPU is the target).

us_per_call is the CPU-interpret wall time — meaningful only as a
regression guard; the chip benchmark (``benchmarks/chip/``) reports their
roofline share on a TPU.
Also runs the SNN runtime throughput comparison (serial VPU path vs
parallel MXU path), the runtime-level analogue of Fig 5.
"""
from __future__ import annotations

import jax
import numpy as np
import jax.numpy as jnp

from repro.core import random_layer
from repro.core.layer import LIFParams
from repro.core.runtime import run_parallel, run_reference, run_serial
from repro.kernels.lif_update import lif_update
from repro.kernels.spike_wdm_matmul import spike_wdm_matmul

from .common import csv_row, timeit


def run():
    print("\n# Pallas kernels (interpret mode on CPU host)")
    # compiled kernels on TPU; off-TPU force the interpreter so the bench
    # still measures the kernel bodies (auto mode would run the jnp refs)
    interp = None if jax.default_backend() == "tpu" else True
    rng = np.random.default_rng(0)
    for (m, k, n) in [(128, 512, 128), (512, 2048, 128)]:
        a = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
        x = jnp.asarray(rng.integers(0, 2, (k, n)), jnp.int8)
        us = timeit(
            lambda: spike_wdm_matmul(a, x, interpret=interp).block_until_ready(),
            iters=5,
        )
        macs = m * k * n
        csv_row(f"kernel_wdm_matmul_{m}x{k}x{n}", us,
                f"gmacs_per_s={macs/us/1e3:.2f}")
    i = jnp.asarray(rng.normal(size=(1024, 128)), jnp.float32)
    v = jnp.zeros((1024, 128), jnp.float32)
    z = jnp.zeros((1024, 128), jnp.float32)
    us = timeit(
        lambda: lif_update(
            i, v, z, alpha=0.5, v_th=1.0, interpret=interp
        )[0].block_until_ready(),
        iters=5,
    )
    csv_row("kernel_lif_update_1024x128", us,
            f"gneuron_updates_per_s={1024*128/us/1e3:.2f}")

    print("\n# SNN runtime throughput (both paradigms, batch=16, T=50)")
    lif = LIFParams(alpha=0.5, v_th=64.0)
    layer = random_layer(256, 256, 0.5, 4, seed=0)
    layer.lif = lif
    spikes = (rng.random((50, 16, 256)) < 0.2).astype(np.float32)
    for name, fn in (
        ("runtime_serial", lambda: run_serial(layer, spikes, lif)),
        ("runtime_parallel", lambda: run_parallel(layer, spikes, lif)),
        ("runtime_reference", lambda: run_reference(layer, spikes, lif)),
    ):
        us = timeit(fn, warmup=1, iters=3)
        steps_per_s = 50 * 16 / (us / 1e6)
        csv_row(name, us, f"batch_timesteps_per_s={steps_per_s:.0f}")


if __name__ == "__main__":
    run()
