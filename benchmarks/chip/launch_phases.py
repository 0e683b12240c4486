#!/usr/bin/env python3
"""Traced runs of one cell with the program's launch phases recorded.

    python3 benchmarks/chip/launch_phases.py --workload gesture.scan_t256 \\
        --seed 7 --seed 8 --seed 9

Each seed is one traced run of the cell (``run.py --trace 1``), with
``jax.profiler.TraceAnnotation`` installed as the program's span sink, so
its ``launch.*`` spans land on the trace's host plane beside the
benchmark's.  For each run one JSON line goes to standard output: the
harness's result (``correct``, ``launch_ms.sim``), and from the trace the
mean milliseconds of each phase per launch, host reads per launch, the
least share of a launch the phases cover, the device clock's offset, and
the longest idle gaps named after the phase the host was in
(``chipbench/phases.py``).  Needs a TPU, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
SRC = HERE.parents[1] / "src"


def measure(workload: str, seed: int, *, require_tpu: bool = True,
            traffic_overrides=None) -> dict:
    """One traced run of ``workload`` with the program's spans recorded."""
    import jax
    from chipbench import harness, phases, trace
    from repro import tracing

    with tempfile.TemporaryDirectory(prefix="chip_phases_") as tmp:
        with tracing.installed(jax.profiler.TraceAnnotation):
            # a traced window lasts the traffic's ``trace_seconds``
            result = harness.run(
                workload, seed, float("inf"), True,
                t_process=time.perf_counter(), require_tpu=require_tpu,
                traffic_overrides=traffic_overrides, trace_dir=Path(tmp))
        reduced = trace.reduce(Path(tmp), "window",
                               known_spans={"window", "sim.launch"})
        events, modules = phases.read(Path(tmp))
    launches = reduced.host_spans("sim.launch")
    offset = phases.clock_offset(events, modules)
    launch_ms = result["metrics"].get("launch_ms.sim", {}).get("value")
    return {"workload": workload, "seed": seed, "correct": result["correct"],
            "launch_ms.sim": launch_ms, "traced_launches": len(launches),
            **phases.summary(phases.per_launch(launches, events)),
            "clock_offset_s": offset,
            "idle_gaps": phases.idle_gaps(reduced, events, offset),
            "idle_gaps_unshifted": phases.idle_gaps(reduced, events, None)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(HERE.parents[1] / ".jax_cache"))
    sys.path[:0] = [str(SRC), str(HERE)]
    for seed in args.seed:
        print(json.dumps(measure(args.workload, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
