"""Recurrent SNN through the full stack: graph IR -> switching -> serving.

The application graph here is NOT a chain — it has a self-loop on the
hidden population and a feedback projection from the output population
back onto the hidden one:

    in(24) ──> hid(32) ──> out(10)
                ^  ^ └loop┘    │
                └──────────────┘  (feedback, one-step-delayed)

1. Train the prejudging classifier on a small paradigm-dataset grid
   (paper §IV-A/B).
2. Build the recurrent graph with explicit populations + projections and
   compile it with the fast-switching system — the classifier prejudges
   **per projection**, exactly as it prejudges chain layers.
3. Execute the fused scan (Pallas kernels in interpret mode — the TPU
   kernel bodies on CPU) and verify bit-identical spike trains against the
   brute-force unrolled numpy reference.
4. Serve variable-length requests through the ServingEngine (no API
   change for graph models) with a partial-bucket age-out, and verify
   every reply equals its solo run.

This is a CPU demo: ``interpret=True`` is hard-coded below, which runs
the kernels through the Pallas interpreter on any backend.  It is not a
chip path; ``chip_smoke.py`` is.

    PYTHONPATH=src python examples/recurrent_snn.py
"""
import time

import numpy as np

from repro.core import (
    Population,
    SwitchingCompiler,
    generate_dataset,
    random_projection,
    train_switch_classifier,
)
from repro.core.layer import LIFParams, SNNNetwork
from repro.core.runtime import network_executable, run_graph_reference
from repro.serving import ServingEngine
from repro.compile_cache import enable_compile_cache

LIF = LIFParams(alpha=0.5, v_th=64.0)


def build_recurrent_net():
    inp = Population("in", 24)
    hid = Population("hid", 32, lif=LIF)    # explicit: 3 in-projections
    out = Population("out", 10, lif=LIF)
    projs = [
        random_projection(inp, hid, 0.4, 2, seed=0),
        random_projection(hid, hid, 0.25, 3, seed=1),   # self-loop
        random_projection(hid, out, 0.5, 2, seed=2),
        random_projection(out, hid, 0.3, 1, seed=3),    # feedback
    ]
    for p in projs:
        p.lif = LIF
    return SNNNetwork(
        populations=[inp, hid, out], projections=projs, name="recurrent",
    )


def main():
    enable_compile_cache()
    print("=== 1. train the prejudging classifier (small grid) ===")
    ds = generate_dataset(
        source_grid=(50, 150),
        target_grid=(100,),
        density_grid=(0.1, 0.5, 0.9),
        delay_grid=(1, 2, 4, 8),
        seed=0,
    )
    clf, acc = train_switch_classifier(ds, seed=0)
    print(f"  {len(ds)} layers; test accuracy {acc * 100:.1f}%")

    print("=== 2. compile the recurrent graph, one compile per projection ===")
    net = build_recurrent_net()
    back = sorted(net.back_edges)
    print(f"  topo order: "
          f"{[net.populations[i].name for i in net.topo_order]}; "
          f"back-edges: {[net.projections[i].name for i in back]}")
    report = SwitchingCompiler("classifier", clf).compile_network(net)
    for cl in report.layers:
        print(f"  {cl.layer_name}: chose {cl.paradigm:8s} -> "
              f"{cl.pe_count} PEs ({cl.n_compilations} compilation)")

    print("=== 3. fused scan (interpret mode) vs unrolled reference ===")
    rng = np.random.default_rng(7)
    spikes = (rng.random((20, 2, net.n_input)) < 0.25).astype(np.float32)
    exe = network_executable(net, report)
    outs = exe.run(spikes, interpret=True)
    ref = run_graph_reference(net, spikes)
    for proj, z, r in zip(net.projections, outs, ref):
        assert np.array_equal(z, r), f"spike mismatch on {proj.name}!"
    print(f"  {sum(int(z.sum()) for z in outs)} spikes across "
          f"{len(outs)} projection outputs — matches the unrolled "
          f"reference bit-for-bit")

    print("=== 4. serve the recurrent model (age-out at 25 ms) ===")
    engine = ServingEngine(
        net, report, micro_batch=4, min_bucket_steps=8,
        interpret=True, max_wait_ms=25.0,
    )
    engine.warmup([8, 16])      # pre-compile the buckets the traffic hits
    rids = {}
    for k in range(6):
        sp = (rng.random((int(rng.integers(4, 16)), net.n_input)) < 0.25
              ).astype(np.float32)
        rids[engine.submit(sp)] = sp
    # continuous steps: full buckets launch at once, the partial tail
    # waits out its age budget before launching under-full
    served = {}
    deadline = time.perf_counter() + 30.0
    while len(served) < len(rids) and time.perf_counter() < deadline:
        served.update(engine.step_continuous())
        time.sleep(0.005)
    assert len(served) == len(rids), "age-out never launched the tail"
    for rid, sp in rids.items():
        x = sp[:, None, :]
        solo = run_graph_reference(net, x)
        for got, want in zip(served[rid], solo):
            assert np.array_equal(got, want[:, 0]), "reply != solo run"
    stats = engine.stats()
    print(f"  served {stats['requests']} requests in {stats['batches']} "
          f"launches ({stats['ageout_launches']} age-out), p95 "
          f"{stats['p95_ms']:.1f} ms — every reply bit-identical to its "
          f"solo run")
    print("done.")


if __name__ == "__main__":
    main()
