"""End-to-end network inference: fused single-scan executor vs per-layer.

Times the same compiled mixed-paradigm report through both execution modes
(interpret mode on the CPU host; TPU is the target), counts
``lower_serial``/``lower_parallel`` invocations, and asserts the fused
path's executable cache lowers each layer exactly once per report.
``run_batch_sweep`` additionally scales the request batch 1/4/16/64
through serial-only vs parallel-only networks and all three serial kernel
modes (event-forced / dense-forced / cost-model auto), pinning the
dense-fallback crossover the executor records in
``CompileReport.serial_forms``.  Both write into ``BENCH_network.json`` at
the repo root so the perf trajectory is tracked across PRs.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import numpy as np

from repro.core import SwitchingCompiler
from repro.core.layer import LIFParams, SNNNetwork, random_layer
from repro.core.runtime import (
    lowering_counts,
    network_executable,
    run_network,
    run_network_layerwise,
)
from repro.core.switching import CompileReport

from .common import csv_row, timeit

_JSON_PATH = Path(__file__).resolve().parents[1] / "BENCH_network.json"


def _merge_json(update: dict) -> None:
    """Update ``BENCH_network.json`` in place, keeping other sections."""
    data = {}
    if _JSON_PATH.exists():
        try:
            data = json.loads(_JSON_PATH.read_text())
        except json.JSONDecodeError:
            data = {}
    data.update(update)
    _JSON_PATH.write_text(json.dumps(data, indent=2) + "\n")


def _mixed_network(sizes, density, delay_range, lif):
    layers = []
    for i in range(len(sizes) - 1):
        l = random_layer(sizes[i], sizes[i + 1], density, delay_range,
                         seed=i, name=f"bench.l{i}")
        l.lif = lif
        layers.append(l)
    net = SNNNetwork(layers=layers, name="bench")
    compiled = [
        SwitchingCompiler("serial" if i % 2 == 0 else "parallel").compile_layer(l)
        for i, l in enumerate(net.layers)
    ]
    return net, CompileReport(layers=compiled)


def run(*, steps: int = 40, batch: int = 8) -> dict:
    print("\n# network executor (fused single-scan vs per-layer, CPU interpret)")
    lif = LIFParams(alpha=0.5, v_th=64.0)
    sizes = [192, 160, 128, 96, 64]          # 4 mixed serial/parallel layers
    net, report = _mixed_network(sizes, density=0.3, delay_range=4, lif=lif)
    rng = np.random.default_rng(0)
    spikes = (rng.random((steps, batch, sizes[0])) < 0.2).astype(np.float32)

    # -- lower counts: fused caches executables on the report ----------------
    before = lowering_counts()
    fused_out = run_network(net, report, spikes)          # warmup + lower
    after_first = lowering_counts()
    run_network(net, report, spikes)                      # cached
    after_second = lowering_counts()
    fused_lowers = sum(after_first[k] - before[k] for k in before)
    fused_relowers = sum(after_second[k] - after_first[k] for k in before)
    n_layers = len(net.layers)
    assert fused_lowers == n_layers, (fused_lowers, n_layers)
    assert fused_relowers == 0, after_second

    base_out = run_network_layerwise(net, report, spikes)  # warmup
    after_base = lowering_counts()
    layerwise_lowers = sum(after_base[k] - after_second[k] for k in before)

    for a, b in zip(fused_out, base_out):
        np.testing.assert_array_equal(a, b)

    # -- throughput: kernel-interpret mode (CPU stand-in for the TPU path) ---
    # every timed closure blocks on its outputs before the clock stops, so
    # async device dispatch cannot under-measure execution time
    us_fused = timeit(
        lambda: jax.block_until_ready(
            run_network(net, report, spikes, interpret=True)
        ),
        warmup=1, iters=5,
    )
    us_layer = timeit(
        lambda: jax.block_until_ready(
            run_network_layerwise(net, report, spikes, interpret=True)
        ),
        warmup=1, iters=5,
    )
    bsteps = steps * batch
    fused_sps = bsteps / (us_fused / 1e6)
    layer_sps = bsteps / (us_layer / 1e6)
    speedup = us_layer / us_fused
    csv_row("network_fused_4layer_interp", us_fused,
            f"batch_timesteps_per_s={fused_sps:.0f}")
    csv_row("network_layerwise_4layer_interp", us_layer,
            f"batch_timesteps_per_s={layer_sps:.0f}")
    csv_row("network_fused_speedup_interp", us_fused,
            f"x_vs_layerwise={speedup:.2f}")

    # -- throughput: auto mode (jnp reference kernels on CPU) ----------------
    us_fused_auto = timeit(
        lambda: jax.block_until_ready(run_network(net, report, spikes)),
        warmup=1, iters=5,
    )
    us_layer_auto = timeit(
        lambda: jax.block_until_ready(
            run_network_layerwise(net, report, spikes)
        ),
        warmup=1, iters=5,
    )
    speedup_auto = us_layer_auto / us_fused_auto
    csv_row("network_fused_4layer_auto", us_fused_auto,
            f"batch_timesteps_per_s={bsteps / (us_fused_auto / 1e6):.0f}")
    csv_row("network_layerwise_4layer_auto", us_layer_auto,
            f"batch_timesteps_per_s={bsteps / (us_layer_auto / 1e6):.0f}")
    csv_row("network_fused_speedup_auto", us_fused_auto,
            f"x_vs_layerwise={speedup_auto:.2f}")

    result = {
        "network": {
            "sizes": sizes,
            "paradigms": [l.paradigm for l in report.layers],
            "steps": steps,
            "batch": batch,
        },
        "interpret_mode": {
            "fused_us_per_run": us_fused,
            "layerwise_us_per_run": us_layer,
            "fused_batch_timesteps_per_s": fused_sps,
            "layerwise_batch_timesteps_per_s": layer_sps,
            "speedup_fused_vs_layerwise": speedup,
        },
        "auto_mode": {
            "fused_us_per_run": us_fused_auto,
            "layerwise_us_per_run": us_layer_auto,
            "speedup_fused_vs_layerwise": speedup_auto,
        },
        "lower_calls_fused_first_run": fused_lowers,
        "lower_calls_fused_repeat_run": fused_relowers,
        "lower_calls_layerwise_per_run": layerwise_lowers,
    }
    _merge_json(result)
    print(f"wrote {_JSON_PATH.name} (speedup {speedup:.2f}x)")
    return result


def _uniform_network(sizes, paradigm, density, delay_range, lif):
    layers = []
    for i in range(len(sizes) - 1):
        l = random_layer(sizes[i], sizes[i + 1], density, delay_range,
                         seed=i, name=f"sweep.{paradigm}.l{i}")
        l.lif = lif
        layers.append(l)
    net = SNNNetwork(layers=layers, name=f"sweep-{paradigm}")
    compiled = [
        SwitchingCompiler(paradigm).compile_layer(l) for l in net.layers
    ]
    return net, CompileReport(layers=compiled)


def run_batch_sweep(
    *, steps: int = 20, batches=(1, 4, 16, 64), sizes=None
) -> dict:
    """Batch-scaling sweep: serial vs parallel paradigm, all kernel modes.

    The serial paradigm's event form (``segment_sum`` scatter) scales
    super-linearly in batch on the host backend; the dense fallback
    restores parallel-like scaling.  ``auto`` lets the cost model pick per
    layer and the sweep records which form the executor chose
    (``CompileReport.serial_forms``) next to the measured curves, so the
    crossover constants stay honest.  Merged into ``BENCH_network.json``
    under ``"batch_sweep"``.
    """
    print("\n# batch scaling sweep (serial kernel forms vs parallel-only)")
    lif = LIFParams(alpha=0.5, v_th=64.0)
    sizes = list(sizes or [192, 160, 128, 96, 64])
    density, delay_range = 0.3, 4
    rng = np.random.default_rng(0)

    nets = {
        p: _uniform_network(sizes, p, density, delay_range, lif)
        for p in ("serial", "parallel")
    }
    exes = {p: network_executable(net, rep) for p, (net, rep) in nets.items()}

    sweep = {
        "sizes": sizes, "density": density, "delay_range": delay_range,
        "steps": steps, "batches": list(batches),
        "crossover_batch_per_serial_layer": [
            round(exes["serial"].cost_model.crossover_batch(
                m.n_rows, m.n_source, m.n_target, m.delay_range), 2)
            for m in exes["serial"].metas
        ],
        "points": [],
    }

    modes = [("serial", "event"), ("serial", "dense"), ("serial", "auto"),
             ("parallel", "auto")]
    for batch in batches:
        spikes = (rng.random((steps, batch, sizes[0])) < 0.2).astype(
            np.float32
        )
        row = {"batch": batch}
        for paradigm, form in modes:
            exe = exes[paradigm]
            us = timeit(
                lambda: jax.block_until_ready(
                    exe.run_device(spikes, serial_form=form)
                ),
                warmup=1, iters=3,
            )
            sps = steps * batch / (us / 1e6)
            key = f"{paradigm}_{form}"
            row[f"{key}_us"] = us
            row[f"{key}_batch_timesteps_per_s"] = sps
            if paradigm == "serial" and form == "auto":
                _, rep = nets["serial"]
                row["auto_forms"] = list(
                    rep.serial_forms[("fused", batch)]
                )
            csv_row(f"network_sweep_{key}_b{batch}", us,
                    f"batch_timesteps_per_s={sps:.0f}")
        sweep["points"].append(row)

    first, last = sweep["points"][0], sweep["points"][-1]
    # the cost model must actually switch across the sweep: event-driven
    # solo requests, dense once batch crosses the recorded crossover
    assert "event" in first["auto_forms"], first
    assert "dense" in last["auto_forms"], last
    ratio = (
        last["parallel_auto_batch_timesteps_per_s"]
        / last["serial_auto_batch_timesteps_per_s"]
    )
    sweep["serial_vs_parallel_at_max_batch"] = ratio
    blowup = (
        last["serial_event_us"] / last["serial_dense_us"]
    )
    sweep["event_vs_dense_at_max_batch"] = blowup
    # dense fallback keeps mixed nets batchable: serial-paradigm
    # throughput at the largest batch stays within 2x of parallel-only
    # (the event form alone blows up super-linearly)
    assert ratio < 2.0, (
        f"serial paradigm {ratio:.2f}x behind parallel at batch "
        f"{last['batch']} — dense fallback not engaging?"
    )
    _merge_json({"batch_sweep": sweep})
    print(
        f"wrote {_JSON_PATH.name} batch_sweep (serial within {ratio:.2f}x "
        f"of parallel at batch {last['batch']}; event form {blowup:.1f}x "
        f"slower than dense there)"
    )
    return sweep


if __name__ == "__main__":
    run()
    run_batch_sweep()
