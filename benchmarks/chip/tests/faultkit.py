"""Whole runs of a cell on the CPU at a small size, with the timed path
broken underneath, for the fault tests.

Each fault is planted in the program (never in the harness) and is one
the cell can have:

* ``state_unchanged`` -- the neuron update returns its state unchanged;
* ``half_batch`` -- only the first half of a launch's lanes is computed,
  and the rest are copies of it;
* ``altered_answer`` -- one spike of each launch is flipped where the
  launch produces it;
* ``control`` -- the bf16 reference in the program's place.

(One chip: no cell has an exchange between chips to leave out.)
"""
import time
from pathlib import Path

import jax.numpy as jnp

from chipbench import control, harness, spec

SOUND = "sound"
#: Test configurations, not in ``BENCHMARK.json``.
DATA = Path(__file__).resolve().parent / "data"


def fixture_bench(*configs: str) -> dict:
    """``BENCHMARK.json`` with the test configurations ``configs`` (files
    ``tests/data/<name>.json``) added, each with one cell per traffic mix
    of the benchmark, named ``<config>.<traffic>``."""
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    mixes = sorted({w["traffic"] for w in bench["workloads"]})
    for name in configs:
        rel = (DATA / f"{name}.json").relative_to(spec.ROOT).as_posix()
        bench["configs"].append({"name": name, "source": "test fixture",
                                 "file": rel, "reduced": [], "why": "test"})
        bench["workloads"] += [{"name": f"{name}.{t}", "config": name,
                                "traffic": t, "chips": 1, "why": "test"}
                               for t in mixes]
    return bench


def _wrap_launches(monkeypatch, post):
    from repro.core.runtime.executor import NetworkExecutable

    for name in ("run_device", "run_batched", "run_temporal"):
        orig = getattr(NetworkExecutable, name)

        def wrapped(self, *a, _orig=orig, **kw):
            return tuple(post(list(_orig(self, *a, **kw))))

        monkeypatch.setattr(NetworkExecutable, name, wrapped)


def _half_batch(outs):
    out = []
    for z in outs:
        b = z.shape[1]
        h = b // 2
        out.append(z.at[:, b - h:].set(z[:, :h]) if h else z)
    return out


def _altered(outs):
    z = outs[-1]
    outs[-1] = z.at[0, 0, 0].set(1.0 - z[0, 0, 0])
    return outs


def plant(monkeypatch, fault: str):
    """Break the program for ``fault``; returns the harness ``tamper``."""
    from repro.core.runtime import executor, temporal_runtime

    if fault == "state_unchanged":
        monkeypatch.setattr(executor, "lif_update", lambda i, v, z, **kw: (v, z))
        monkeypatch.setattr(temporal_runtime, "lif_parallel_scan",
                            lambda c, **kw: jnp.zeros_like(c))
    elif fault == "half_batch":
        _wrap_launches(monkeypatch, _half_batch)
    elif fault == "altered_answer":
        _wrap_launches(monkeypatch, _altered)
    elif fault == "control":
        return control.bf16_answers
    elif fault != SOUND:
        raise ValueError(fault)
    return None


def run(cell: str, fault: str, monkeypatch, *, seconds=1.0, seed=2**31 + 11,
        config=None, traffic=None, bench=None) -> dict:
    tamper = plant(monkeypatch, fault)
    return harness.run(cell, seed, seconds, False,
                       t_process=time.perf_counter(), require_tpu=False,
                       config_overrides=config, traffic_overrides=traffic,
                       tamper=tamper, bench=bench)
