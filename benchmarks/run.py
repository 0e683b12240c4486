# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark driver — one section per paper table/figure:

  Table I  -> bench_cost_model       (DTCM byte model + compile latency)
  Fig 3    -> bench_marginals        (marginal win-rate distributions)
  Fig 4    -> bench_classifiers      (12-classifier accuracy comparison)
  Fig 5    -> bench_switching        (avg PEs vs delay: 4 policies)
  §IV-C    -> bench_gesture          (2048-20-4 gesture model PEs)
  §IV motivation -> bench_compile_time (prejudge vs compile-both)
  kernels  -> bench_kernels          (Pallas kernels + runtime throughput)
  runtime  -> bench_network          (fused single-scan vs per-layer -> BENCH_network.json)
  batching -> bench_network.run_batch_sweep (serial kernel forms vs parallel
              across batch 1/4/16/64 -> BENCH_network.json "batch_sweep")
  sparse   -> bench_sparse             (event/sparse/dense kernel forms across
              size 1k-50k at SpiNNCer densities -> BENCH_network.json
              "sparse_sweep")
  temporal -> bench_temporal           (whole-train temporal paradigm vs the
              fused per-step scan across T=16-512 -> BENCH_network.json
              "temporal_sweep")
  serving  -> bench_serving          (batched Poisson serving -> BENCH_serving.json)
  placement-> bench_placement        (NoC cut traffic: search vs round-robin
              -> BENCH_network.json "placement")
  scaffold -> bench_scaffold         (cerebellum generator scale trajectory
              1k-100k -> BENCH_network.json "scaffold_scale")

``PYTHONPATH=src python -m benchmarks.run [--fast] [--seeds N]``
"""
from __future__ import annotations

import argparse
import time

from repro.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3,
                    help="classifier seeds for Fig 4 (paper uses 20)")
    ap.add_argument("--fast", action="store_true",
                    help="subsample classifier training (quick check)")
    args = ap.parse_args()

    from . import (
        bench_classifiers,
        bench_compile_time,
        bench_cost_model,
        bench_gesture,
        bench_kernels,
        bench_marginals,
        bench_network,
        bench_placement,
        bench_scaffold,
        bench_serving,
        bench_sparse,
        bench_switching,
        bench_temporal,
    )

    t0 = time.time()
    print("name,us_per_call,derived")
    bench_cost_model.run()
    bench_marginals.run()
    bench_classifiers.run(seeds=args.seeds, fast=args.fast)
    bench_switching.run()
    bench_gesture.run()
    bench_compile_time.run()
    bench_kernels.run()
    bench_network.run()
    bench_network.run_batch_sweep()
    bench_sparse.run(fast=args.fast)
    bench_temporal.run(fast=args.fast)
    bench_serving.run()
    bench_placement.run()
    bench_scaffold.run(fast=args.fast)
    print(f"\nall benchmarks done in {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
