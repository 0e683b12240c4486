"""Faults the microcircuit's check must see, on the CPU at the size
``test_chipbench_microcircuit.py`` cuts it to, in the style of
``faultkit.py``: each planted fault makes ``correct`` false.

* ``altered_answer`` -- one spike of each launch flipped where the
  launch produces it;
* a reference without the refractory period;
* a reference that delivers back-edge spikes at d + 1, as the program's
  feedback ring would without the stored ``d - 1``;
* the control: the reference in bfloat16 in the program's place.
"""
import copy

import pytest

import faultkit
from chipbench import graph, spec
from test_chipbench_microcircuit import CELL, SEED, SHORT, SMALL


def _replaced(monkeypatch, produce):
    """A ``tamper`` that puts ``produce(sim, x)`` in the program's place."""
    def tamper(sim):
        with monkeypatch.context() as m:
            sim.replace_answers(lambda x: produce(m, sim, x))
    return tamper


def _without_refractoriness(m, sim, x):
    sp = copy.copy(sim.h.spec)
    sp.pops = [copy.copy(p) for p in sp.pops]
    for p in sp.pops:
        if not p.is_input:
            p.params = dict(p.params, t_ref=0)
    return sim.h.reference.simulate(sp, x)


def _back_edges_a_step_late(m, sim, x):
    m.setattr(graph, "BACK_EDGE_LAG", 2)
    return sim.h.reference.simulate(sim.h.spec, x)


def _run(monkeypatch, fault, tamper=None, seed=SEED + 10):
    import time
    from chipbench import harness

    faultkit.plant(monkeypatch, fault)
    if fault == "control":
        from chipbench import control
        tamper = control.bf16_answers
    return harness.run(CELL, seed, 1.0, False, t_process=time.perf_counter(),
                       require_tpu=False, config_overrides=SMALL,
                       traffic_overrides=SHORT, tamper=tamper)


@pytest.mark.parametrize("fault", ["altered_answer", "control"])
def test_program_faults_are_not_correct(fault, monkeypatch):
    r = _run(monkeypatch, fault)
    assert r["correct"] is False, r["checks"]
    assert r["checks"]["spike_mismatches"]["value"] > 0


@pytest.mark.parametrize("produce", [_without_refractoriness,
                                     _back_edges_a_step_late])
def test_a_reference_that_departs_from_the_model_is_not_correct(
        produce, monkeypatch):
    r = _run(monkeypatch, faultkit.SOUND,
             tamper=_replaced(monkeypatch, produce))
    assert r["correct"] is False, r["checks"]
    assert r["checks"]["spike_mismatches"]["value"] > 0
