#!/usr/bin/env python3
"""Smoke run of the SNN system's main paths on one TPU chip.

    python3 chip_smoke.py

Run from the repository root on a machine with a TPU; it puts ``src`` on
``sys.path`` itself and needs no environment variable.  One process, one
chip.  Phases:

1. **device** -- refuses to run unless JAX's first device is a TPU.
2. **gesture_served** -- the source paper's 2048-20-4 gesture network,
   compiled with the trained AdaBoost prejudging classifier, plus its
   all-parallel compilation as a second model, serves 32 mixed-shape
   requests through ``ServingEngine`` at micro-batch 8 (full buckets take
   the vmapped path, partial ones the fused scan).  Every reply must be
   bit-identical to ``run_graph_reference`` on that request alone, with
   no shed or failed reply and no supervisor retry, degradation or
   breaker trip; the launched scan must hold a Pallas kernel
   (``tpu_custom_call``), and the sparse form must run at batch 8 (the
   classifier keeps the second layer serial).
3. **gesture_temporal** -- ``run_temporal`` at T=256, batch 8 (the
   ``lif_parallel_scan`` kernel), bit-identical to the oracle.
4. **scaffold_10k** -- the cerebellum scaffold at 10^4 neurons, T=16,
   batch 1, bit-identical to the oracle (which densifies, so it stops
   short of 10^5).
5. **scaffold_100k** -- 10^5 neurons (2.1M synapses), T=16, batch 1 and
   8: over-cap projections compile serial and launch on event or sparse
   forms, mean rates stay in [0, 0.95) with spikes present, and a forced
   sparse run at batch 1 equals the automatic (event-form) one.

Each phase prints one JSON line (seconds, set-up and compile seconds,
forms chosen, ``peak_bytes_in_use``).  The last line of standard output
is ``{"ok": true, "device": {...}}``; any failed check exits non-zero
before it.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

#: The gesture network of the source paper (§IV-C), as ``examples/serve_snn.py``.
GESTURE_SIZES = [2048, 20, 4]
MICRO_BATCH = 8
ALT_MODEL = "parallel-all"


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def report_phase(dev, name: str, **fields) -> None:
    print(json.dumps({"phase": name, **fields,
                      "peak_bytes_in_use": peak_bytes(dev)}), flush=True)


def gesture_network():
    from repro.core import feedforward_network
    from repro.core.layer import LIFParams

    net = feedforward_network(GESTURE_SIZES, density=0.0316, delay_range=1,
                              seed=0, name="gesture")
    for layer in net.layers:
        layer.lif = LIFParams(alpha=0.5, v_th=64.0)
    return net


def solo_input(spikes, n_input: int):
    """One request as a batch-1 ``(steps, 1, n_input)`` train."""
    import numpy as np

    x = np.zeros((spikes.shape[0], 1, n_input), np.float32)
    x[:, 0, : spikes.shape[1]] = spikes
    return x


def traffic(rng, n_input: int):
    """32 requests: per model, full buckets of 8 at bucket 16 (vmapped
    path) and a partial bucket at 32 (fused path); widths vary."""
    widths = [n_input, 3 * n_input // 4, n_input // 2]
    plan = [("default", 9, 16, 16), ("default", 17, 32, 5),
            ("parallel-all", 9, 16, 8), ("parallel-all", 17, 32, 3)]
    out = []
    for model, lo, hi, count in plan:
        for _ in range(count):
            steps = int(rng.integers(lo, hi + 1))
            width = int(rng.choice(widths))
            out.append((model, (rng.random((steps, width)) < 0.2)
                        .astype("float32")))
    return out


def phase_gesture_served(dev):
    import numpy as np

    from repro.core import (SwitchingCompiler, load_or_generate,
                            train_switch_classifier)
    from repro.core.runtime import network_executable, run_graph_reference
    from repro.serving import ServingEngine

    t0 = time.perf_counter()
    clf, acc = train_switch_classifier(
        load_or_generate(extended=True, progress=False), seed=0)
    net = gesture_network()
    switched = SwitchingCompiler("classifier", clf).compile_network(net)
    parallel = SwitchingCompiler("parallel").compile_network(net)
    engine = ServingEngine(net, switched, micro_batch=MICRO_BATCH,
                           min_bucket_steps=8)
    setup_s = time.perf_counter() - t0

    t1 = time.perf_counter()
    engine.warmup([16, 32])
    engine.register_model(net, parallel, ALT_MODEL, warm_steps=[16, 32])
    compile_s = time.perf_counter() - t1

    requests = traffic(np.random.default_rng(0), net.n_input)
    t2 = time.perf_counter()
    rids = {engine.submit(sp, model=m): sp for m, sp in requests}
    served = engine.drain()
    serve_s = time.perf_counter() - t2

    check(set(served) == set(rids), "not every request got a reply")
    for rid, spikes in rids.items():
        reply = served[rid]
        check(isinstance(reply, list),
              f"request {rid} got {type(reply).__name__}: {reply}")
        ref = run_graph_reference(net, solo_input(spikes, net.n_input))
        check(all(np.array_equal(got, want[:, 0])
                  for got, want in zip(reply, ref)),
              f"request {rid}: served reply differs from the oracle")
    stats = engine.stats()
    sup = stats["supervisor"]
    for key, value in (("shed", stats["shed"]), ("failed", stats["failed"]),
                       ("retries", sup["retries"]),
                       ("degraded_launches", sup["degraded_launches"]),
                       ("breaker_trips", sup["breaker_trips"]),
                       ("bisections", sup["bisections"]),
                       ("validation_failures", sup["validation_failures"])):
        check(value == 0, f"serving counter {key} = {value}, expected 0")

    forms = {f"{name}|{path}|{batch}": list(f)
             for name, rep in (("default", switched), (ALT_MODEL, parallel))
             for (path, batch), f in rep.serial_forms.items()}
    check(any("sparse" in f for k, f in forms.items()
              if k.endswith(f"|{MICRO_BATCH}")),
          f"no launch at batch {MICRO_BATCH} ran the sparse form: {forms}")
    paradigms = [layer.paradigm for layer in switched.layers]
    exe = network_executable(net, switched)
    text = exe.lower(16, MICRO_BATCH, path="vmap").compile().as_text()
    check("tpu_custom_call" in text, "launched scan holds no Pallas kernel")

    report_phase(dev, "gesture_served", seconds=time.perf_counter() - t0,
                 setup_s=setup_s, compile_s=compile_s, serve_s=serve_s,
                 classifier_acc=acc, paradigms=paradigms, forms=forms,
                 requests=len(rids), launches=stats["batches"])


def phase_gesture_temporal(dev, steps: int = 256, batch: int = MICRO_BATCH):
    import jax
    import numpy as np

    from repro.core import SwitchingCompiler
    from repro.core.runtime import network_executable, run_graph_reference

    t0 = time.perf_counter()
    net = gesture_network()
    report = SwitchingCompiler("serial").compile_network(net)
    exe = network_executable(net, report)
    spikes = (np.random.default_rng(2).random((steps, batch, net.n_input))
              < 0.2).astype(np.float32)
    setup_s = time.perf_counter() - t0

    t1 = time.perf_counter()
    jax.block_until_ready(exe.run_temporal(spikes))
    compile_s = time.perf_counter() - t1
    t2 = time.perf_counter()
    got = [np.asarray(z) for z in exe.run_temporal(spikes)]
    run_s = time.perf_counter() - t2

    want = run_graph_reference(net, spikes)
    check(all(np.array_equal(a, b) for a, b in zip(got, want)),
          "temporal gesture run differs from the oracle")
    rec = report.temporal[(batch, steps)]
    check(all(r == 0 for r in rec.residual.values()),
          f"temporal fixed point did not converge: {rec.residual}")
    report_phase(dev, "gesture_temporal", seconds=time.perf_counter() - t0,
                 setup_s=setup_s, compile_s=compile_s, run_s=run_s,
                 forms=list(report.serial_forms[("temporal", batch)]),
                 modes={str(k): v for k, v in rec.modes.items()},
                 iterations={str(k): v for k, v in rec.iterations.items()},
                 spikes=int(sum(z.sum() for z in got)))


def build_scaffold(n_neurons: int):
    from repro.scaffold import build_cerebellum, compile_scaffold

    sc = build_cerebellum(n_neurons, seed=2024)
    return sc, compile_scaffold(sc)


def timed_run(exe, spikes, **kw):
    """(host trains, first-launch seconds incl. compile, steady seconds)."""
    import jax
    import numpy as np

    t0 = time.perf_counter()
    jax.block_until_ready(exe.run_device(spikes, **kw))
    first_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    outs = [np.asarray(z) for z in exe.run_device(spikes, **kw)]
    return outs, first_s, time.perf_counter() - t1


def phase_scaffold_oracle(dev, n_neurons: int = 10_000, steps: int = 16):
    import numpy as np

    from repro.core.runtime import network_executable, run_graph_reference

    t0 = time.perf_counter()
    sc, report = build_scaffold(n_neurons)
    exe = network_executable(sc.network, report)
    spikes = sc.stimulus(steps, 1, seed=7)
    setup_s = time.perf_counter() - t0

    got, compile_s, run_s = timed_run(exe, spikes)
    want = run_graph_reference(sc.network, spikes)
    check(all(np.array_equal(a, b) for a, b in zip(got, want)),
          f"{n_neurons}-neuron scaffold differs from the oracle")
    report_phase(dev, f"scaffold_{n_neurons}", seconds=time.perf_counter() - t0,
                 setup_s=setup_s, compile_s=compile_s, run_s=run_s,
                 neurons=sc.total_neurons, synapses=sc.total_synapses,
                 paradigms=[l.paradigm for l in report.layers],
                 forms=list(report.serial_forms[("fused", 1)]),
                 spikes=int(sum(z.sum() for z in got)))


def phase_scaffold_scale(dev, n_neurons: int = 100_000, steps: int = 16,
                         batches=(1, MICRO_BATCH)):
    import numpy as np

    from repro.core.layer import DENSE_ELEMENT_CAP
    from repro.core.runtime import network_executable, profile_outputs

    t0 = time.perf_counter()
    sc, report = build_scaffold(n_neurons)
    net = sc.network
    exe = network_executable(net, report)
    setup_s = time.perf_counter() - t0
    over_cap = [i for i, e in enumerate(net.projections)
                if e.n_source * e.n_target > DENSE_ELEMENT_CAP]
    paradigms = [l.paradigm for l in report.layers]
    for i in over_cap:
        check(paradigms[i] == "serial",
              f"over-cap projection {net.projections[i].name} compiled "
              f"{paradigms[i]}")

    per_batch, first = {}, None
    for batch in batches:
        spikes = sc.stimulus(steps, batch, seed=7)
        outs, compile_s, run_s = timed_run(exe, spikes)
        first = first or (spikes, outs)
        forms = report.serial_forms[("fused", batch)]
        for i in over_cap:
            check(forms[i] in ("event", "sparse"),
                  f"over-cap projection {net.projections[i].name} launched "
                  f"on {forms[i]!r} at batch {batch}")
        rates = profile_outputs(net, spikes, outs).rates()
        check(all(0.0 <= r < 0.95 for r in rates.values()),
              f"scaffold rates out of range at batch {batch}: {rates}")
        check(sum(z.sum() for z in outs) > 0,
              f"scaffold produced no spikes at batch {batch}")
        per_batch[batch] = dict(compile_s=compile_s, run_s=run_s,
                                forms=list(forms),
                                rates={k: round(v, 5) for k, v in
                                       sorted(rates.items())})

    spikes, outs = first
    sparse, compile_s, run_s = timed_run(exe, spikes, serial_form="sparse")
    check(all(np.array_equal(a, b) for a, b in zip(sparse, outs)),
          f"forced-sparse scaffold run differs from the auto run at "
          f"batch {batches[0]}")
    per_batch["sparse_forced"] = dict(
        batch=batches[0], compile_s=compile_s, run_s=run_s,
        forms=list(report.serial_forms[("fused", batches[0])]))
    report_phase(dev, f"scaffold_{n_neurons}", seconds=time.perf_counter() - t0,
                 setup_s=setup_s, neurons=sc.total_neurons,
                 synapses=sc.total_synapses, paradigms=paradigms,
                 steps=steps, batches=per_batch)


def main() -> int:
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform} ({dev.device_kind})", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    print(json.dumps({"phase": "device", "kind": dev.device_kind,
                      "count": len(jax.devices()), "cache_dir": cache_dir}),
          flush=True)
    phase_gesture_served(dev)
    phase_gesture_temporal(dev)
    phase_scaffold_oracle(dev)
    phase_scaffold_scale(dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
