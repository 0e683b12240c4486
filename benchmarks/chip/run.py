#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload gesture.scan_t256 \\
        --seed 7 --seconds 30 --trace 0

Run from the root of a checkout on a machine with a TPU.  The cell's
configuration, traffic mix and metrics are found by the names
``BENCHMARK.json`` gives them.  The run builds the network from the seed,
compiles and warms up every shape the window uses (all of it set-up),
measures for ``--seconds``, checks what the window produced against the
plain reference, and prints one JSON object as the last line of standard
output: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and with ``--trace 1`` the ``breakdown``), then ``checks``, each number
compared beside its limit.  The same checks are the last lines of
standard error.

Exits 1 without a result when JAX finds no TPU, or fewer chips than the
cell asks for, and 2 when the checkout holds no program under ``src``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
SRC = HERE.parents[1] / "src"
#: JAX's persistent compilation cache: a fixed directory in the checkout,
#: so only a cell's first run there compiles, and nothing is shared with
#: another checkout.
CACHE = HERE.parents[1] / ".jax_cache"


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (SRC / "repro").is_dir():
        print(f"run.py: no program under {SRC}", file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    sys.path[:0] = [str(SRC), str(HERE)]
    from chipbench import harness

    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_process=T_PROCESS)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
