"""Quickstart: the paper's pipeline in miniature, on CPU, in ~1 minute.

1. Generate a small paradigm dataset by compiling both paradigms over a
   layer-character grid (paper §IV-A).
2. Train the AdaBoost prejudging classifier (paper §IV-B).
3. Compile an SNN with the fast-switching system — one compilation per
   layer, paradigm chosen BEFORE compiling (paper §IV-C).
4. Execute the compiled network on the JAX runtimes and verify the spike
   trains against the dense LIF oracle.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import (
    SwitchingCompiler,
    feedforward_network,
    generate_dataset,
    train_switch_classifier,
)
from repro.core.layer import LIFParams
from repro.core.runtime import run_network, run_reference
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    print("=== 1. dataset: compile both paradigms over a character grid ===")
    ds = generate_dataset(
        source_grid=(50, 150, 300, 450),
        target_grid=(100, 300),
        density_grid=(0.1, 0.3, 0.6, 0.9),
        delay_grid=(1, 2, 4, 8, 16),
        seed=0,
    )
    print(f"  {len(ds)} layers; parallel wins {ds.labels.mean()*100:.0f}%")

    print("=== 2. train the prejudging classifier (AdaBoost) ===")
    clf, acc = train_switch_classifier(ds, seed=0)
    print(f"  test accuracy {acc*100:.1f}% (paper: 91.69%)")

    print("=== 3. fast-switching compilation (one compile per layer) ===")
    lif = LIFParams(alpha=0.5, v_th=64.0)
    net = feedforward_network([200, 150, 80], density=0.5, delay_range=2,
                              seed=1, name="demo")
    for l in net.layers:
        l.lif = lif
    report = SwitchingCompiler("classifier", clf).compile_network(net)
    for cl in report.layers:
        print(f"  {cl.layer_name}: chose {cl.paradigm:8s} -> "
              f"{cl.pe_count} PEs ({cl.n_compilations} compilation)")
    for policy in ("serial", "parallel", "ideal"):
        rep = SwitchingCompiler(policy).compile_network(net)
        print(f"  [{policy:8s}] total {rep.total_pes} PEs, "
              f"{rep.total_compilations} compilations")
    print(f"  [switched] total {report.total_pes} PEs, "
          f"{report.total_compilations} compilations")

    print("=== 4. execute on the JAX runtimes, verify vs dense oracle ===")
    rng = np.random.default_rng(0)
    spikes = (rng.random((20, 4, 200)) < 0.25).astype(np.float32)
    outs = run_network(net, report, spikes)
    x = spikes
    for layer, z in zip(net.layers, outs):
        ref = run_reference(layer, x, lif)
        assert np.array_equal(z, ref), "spike mismatch!"
        print(f"  {layer.name}: {int(z.sum())} spikes — matches oracle "
              f"bit-for-bit")
        x = ref
    print("done.")


if __name__ == "__main__":
    main()
