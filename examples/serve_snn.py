"""End-to-end driver: continuous-batching SNN inference *service*.

Simulates live multi-tenant traffic against the gesture-style network
(paper §IV-C): independent requests with varying ``(steps, n_in)``
shapes, mixed priorities, and per-request deadlines arrive as a Poisson
process and flow through the serving subsystem —

    RequestQueue -> ShapeBucketingScheduler -> ExecutablePool -> fused scan
     (priority/EDF)   (slot-level admission)    (multi-model routing)

The switching compiler picks the paradigm per layer with the
extended-grid classifier; the serving engine admits each request into a
compatible open in-flight bucket *between scan launches* (continuous
batching — no request waits out a full drain wave), micro-batches it
with its bucket peers, and runs the whole mixed serial/parallel network
as one jitted scan per launch.  A second registered model (the
all-parallel compilation of the same network) serves part of the
traffic to exercise multi-model routing.  Steady-state traffic re-uses
warmed jit entries — zero re-lowerings, zero re-traces — and every
response is bit-identical to running that request alone (the executor's
step-count mask keeps the padding inert).

    PYTHONPATH=src python examples/serve_snn.py [--requests 64] [--steps 50]
"""
import argparse
import time

import jax
import numpy as np

from repro.core import (
    SwitchingCompiler,
    feedforward_network,
    load_or_generate,
    train_switch_classifier,
)
from repro.core.layer import LIFParams
from repro.core.runtime import network_executable
from repro.serving import ServingEngine, ShedReply
from repro.compile_cache import enable_compile_cache

N_INPUT = 2048
ALT_MODEL = "parallel-all"      # second tenant: all-parallel compilation


def poisson_traffic(rng, n_requests, base_steps, rate, arrival_hz):
    """Poisson arrivals of variable-length, mixed-priority requests.

    Every request draws its own step count from ``[base/2, 3*base/2]``
    and one of three input widths — the unconstrained-shape traffic a
    jit cache cannot survive without the scheduler's bucketing.  ~30%
    route to the second registered model; ~25% are interactive
    (priority 2, 2 s deadline), the rest bulk (priority 0).
    """
    lo = max(2, base_steps // 2)
    hi = max(lo, base_steps + base_steps // 2)
    width_mix = [N_INPUT, 3 * N_INPUT // 4, N_INPUT // 2]
    arrivals = np.cumsum(rng.exponential(1.0 / arrival_hz, n_requests))
    traffic = []
    for t_arr in arrivals:
        steps = int(rng.integers(lo, hi + 1))
        n_in = int(rng.choice(width_mix))
        spikes = (rng.random((steps, n_in)) < rate).astype(np.float32)
        model = ALT_MODEL if rng.random() < 0.3 else "default"
        interactive = rng.random() < 0.25
        traffic.append((
            float(t_arr), spikes, model,
            2 if interactive else 0,
            2000.0 if interactive else None,
        ))
    return (lo, hi), traffic


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64,
                    help="number of simulated inference requests")
    ap.add_argument("--steps", type=int, default=50,
                    help="base timesteps per request (mix spans 0.5x-1.5x)")
    ap.add_argument("--rate", type=float, default=0.2, help="input spike rate")
    ap.add_argument("--arrival-hz", type=float, default=500.0,
                    help="Poisson arrival rate of the simulated traffic")
    ap.add_argument("--micro-batch", type=int, default=8,
                    help="padded micro-batch width per bucket")
    args = ap.parse_args()

    print("loading classifier (cached 16k dataset + extended grid)...")
    clf, acc = train_switch_classifier(
        load_or_generate(extended=True, progress=True), seed=0)
    print(f"  prejudging classifier ready (acc {acc*100:.1f}%)")

    lif = LIFParams(alpha=0.5, v_th=64.0)
    net = feedforward_network([N_INPUT, 20, 4], density=0.0316, delay_range=1,
                              seed=0, name="gesture")
    for l in net.layers:
        l.lif = lif

    reports = {
        "serial": SwitchingCompiler("serial").compile_network(net),
        "parallel": SwitchingCompiler("parallel").compile_network(net),
        "switched": SwitchingCompiler("classifier", clf).compile_network(net),
    }
    for name, rep in reports.items():
        choice = "/".join(l.paradigm[:3] for l in rep.layers)
        print(f"  {name:8s}: {rep.total_pes:3d} PEs ({choice}), "
              f"{rep.total_compilations} host compilations")

    rng = np.random.default_rng(0)
    (lo, hi), traffic = poisson_traffic(
        rng, args.requests, args.steps, args.rate, args.arrival_hz)
    distinct = len({sp.shape for _, sp, *_ in traffic})

    engine = ServingEngine(net, reports["switched"],
                           micro_batch=args.micro_batch, min_bucket_steps=8)
    n_warmed = engine.warmup(list(range(lo, hi + 1)))
    # second tenant: the all-parallel compilation of the same network
    engine.register_model(net, reports["parallel"], ALT_MODEL,
                          warm_steps=list(range(lo, hi + 1)))
    print(f"serving engine ready: 2 models, warmed {n_warmed} bucket shapes "
          f"covering steps {lo}..{hi} "
          f"({distinct} distinct request shapes inbound)")

    # -- Poisson traffic, continuous batching --------------------------------
    print(f"serving {args.requests} Poisson-arrival requests "
          f"({args.arrival_hz:.0f} req/s, micro-batch {args.micro_batch}, "
          f"continuous admission)...")
    results = {}
    idx, t0 = 0, time.perf_counter()
    while idx < len(traffic) or not engine.queue.empty() \
            or engine.scheduler.has_open():
        now = time.perf_counter() - t0
        while idx < len(traffic) and traffic[idx][0] <= now:
            t_arr, spikes, model, prio, deadline = traffic[idx]
            rid = engine.submit(spikes, model=model, priority=prio,
                                deadline_ms=deadline)
            results[rid] = (spikes, model)
            idx += 1
        if engine.queue.empty() and not engine.scheduler.has_open():
            time.sleep(0.001)           # idle until the next arrival is due
            continue
        engine.step_continuous()        # admit arrivals, launch ONE bucket
    stats = engine.stats()
    print(f"  served {stats['requests']} requests in "
          f"{stats['batches']} launches "
          f"(mean occupancy {stats['mean_batch_occupancy']:.1f}, "
          f"padding overhead {stats['padding_overhead']:.2f}x, "
          f"{stats['shed']} shed)")
    print(f"  latency p50 {stats['p50_ms']:.1f} ms, "
          f"p95 {stats['p95_ms']:.1f} ms "
          f"(mean queue wait {stats['mean_queue_wait_ms']:.1f} ms)")
    for prio, cls in stats["latency_by_priority"].items():
        print(f"    priority {prio}: {cls['requests']} requests, "
              f"p50 {cls['p50_ms']:.1f} ms, p95 {cls['p95_ms']:.1f} ms")
    if stats["deadline_miss_rate"] is not None:
        print(f"  deadline-miss rate "
              f"{stats['deadline_miss_rate']*100:.1f}%")
    print(f"  throughput {stats['throughput_request_steps_per_s']:,.0f} "
          f"request-steps/s, bucket-hit rate "
          f"{stats['bucket_hit_rate']*100:.0f}%, "
          f"{stats['relowerings']} re-lowerings")
    for name, c in stats["by_model"].items():
        print(f"    model {name:12s}: {c['bucket_hits']} hits / "
              f"{c['bucket_misses']} misses, "
              f"{c['warm_shapes']} warm shapes")

    # -- padding inertness: a served reply == running the request alone ------
    rid, (spikes, model) = next(
        (r, v) for r, v in results.items() if v[1] == "default"
    )
    exe = network_executable(net, reports["switched"])
    solo_in = np.zeros((spikes.shape[0], 1, N_INPUT), np.float32)
    solo_in[:, 0, : spikes.shape[1]] = spikes
    solo = exe.run(solo_in)
    served = engine.results[rid]
    assert not isinstance(served, ShedReply)
    same = all(
        np.array_equal(a, b[:, 0]) for a, b in zip(served, solo)
    )
    print(f"served output bit-identical to running the request alone: {same}")

    # -- batched serving vs one-request-at-a-time dispatch -------------------
    # The naive server jits per request shape: with continuously variable
    # step counts every novel (steps, n_in) pays a fresh trace + XLA
    # compile, while the engine's bucketing folds all of them onto the few
    # warmed shapes.  Both sides host-materialize their replies and block
    # on the device before the clock stops.
    solo_inputs = []
    for _, spikes, *_ in traffic:
        x = np.zeros((spikes.shape[0], 1, N_INPUT), np.float32)
        x[:, 0, : spikes.shape[1]] = spikes
        solo_inputs.append(x)
    t0 = time.perf_counter()
    for x in solo_inputs:
        jax.block_until_ready(exe.run(x))
    dt_solo = time.perf_counter() - t0

    for _, spikes, *_ in traffic:
        engine.submit(spikes)
    t0 = time.perf_counter()
    engine.drain()              # host-materializes every reply
    dt_batched = time.perf_counter() - t0
    true_steps = sum(sp.shape[0] for _, sp, *_ in traffic)
    print(f"replaying the {args.requests} requests: bucketed+batched "
          f"{dt_batched*1e3:.1f} ms ({true_steps/dt_batched:,.0f} "
          f"request-steps/s) vs one-at-a-time dispatch "
          f"({distinct} jit shapes) {dt_solo*1e3:.1f} ms "
          f"({true_steps/dt_solo:,.0f} request-steps/s) -> "
          f"{dt_solo/dt_batched:.1f}x")

    # classify each request by its most active output neuron
    klass = [int(res[-1].sum(axis=0).argmax())
             for res in list(engine.results.values())[:16]
             if not isinstance(res, ShedReply)]
    print(f"predicted gesture classes (first 16 requests): {klass}")


if __name__ == "__main__":
    main()
