#!/usr/bin/env python3
"""Read a cell's sound and control numbers on several seeds.

    python3 benchmarks/chip/control.py --workload gesture.scan_t256 \\
        --seconds 10 --seeds 11 12 13

For each seed, one run of the cell as ``run.py`` makes it, with a short
window: the program's own check (the sound reading), then the same check
with the bf16 reference in the program's place (the control).  Prints
one JSON line per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
SRC = HERE.parents[1] / "src"
#: JAX's persistent compilation cache: a fixed directory in the checkout,
#: so only a cell's first run there compiles, and nothing is shared with
#: another checkout.
CACHE = HERE.parents[1] / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    sys.path[:0] = [str(SRC), str(HERE)]
    from chipbench import harness
    from chipbench.control import SoundThenControl

    for seed in args.seeds:
        tamper = SoundThenControl()
        result = harness.run(args.workload, seed, args.seconds, False,
                             t_process=time.perf_counter(), tamper=tamper)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "attempted": result["attempted"], "device": result["device"],
            "sound": {k: v for k, (v, _) in tamper.sound.items()},
            "control": {k: c["value"] for k, c in result["checks"].items()},
            "control_correct": result["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
