"""Fig 4 walk-through: train the 12-classifier zoo on the paradigm dataset
and pick the switching classifier — the paper's model-selection step.

    PYTHONPATH=src python examples/classifier_selection.py [--seeds 3]
"""
import argparse

from benchmarks.bench_classifiers import run as fig4_run
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    results = fig4_run(seeds=args.seeds, fast=args.fast)
    best = max(results, key=lambda n: results[n][0])
    print(f"\nselected switching classifier: {best} "
          f"({results[best][0]*100:.2f}%)")
    print("(the paper selects Adaptive Boost at 91.69% on ITS compiler's "
          "dataset; rankings depend on the compiler's decision boundary)")


if __name__ == "__main__":
    main()
