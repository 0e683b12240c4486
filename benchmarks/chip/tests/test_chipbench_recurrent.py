"""A configuration that brings its own network -- the recurrent fixture
under ``tests/data/``, with its own generator and the graph reference --
runs whole through the harness on the CPU: sound, it is correct; with
each fault planted, it is not."""
import json
import time

import pytest

import faultkit
from chipbench import graph, harness, simulate

BENCH = faultkit.fixture_bench("recurrent", "recurrent_wide")
SHORT = {"steps": 32}


def _flip_in_I(outs):
    """One spike of population I flipped where the launch produces it:
    I's train is the output of projection E -> I, the third."""
    z = outs[2]
    outs[2] = z.at[5, 0, 0].set(1.0 - z[5, 0, 0])
    return outs


def _without_back_edge_lag(monkeypatch):
    """Answers of a program that delivers a back-edge spike at t + d, not
    t + d + 1."""
    def tamper(driver):
        with monkeypatch.context() as m:
            m.setattr(graph, "BACK_EDGE_LAG", 0)
            driver.replace_answers(lambda x: graph.simulate(driver.h.spec, x))
    return tamper


@pytest.mark.parametrize("cell", ["recurrent.scan_t256",
                                  "recurrent.window_t256"])
def test_recurrent_fixture_is_correct(cell, monkeypatch):
    r = faultkit.run(cell, faultkit.SOUND, monkeypatch, traffic=SHORT,
                     bench=BENCH)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1
    assert set(r["metrics"]) == {"setup_s"}


@pytest.mark.parametrize("fault", ["control", "state_unchanged",
                                   "half_batch", "altered_answer"])
def test_recurrent_fixture_fails_each_program_fault(fault, monkeypatch):
    r = faultkit.run("recurrent.scan_t256", fault, monkeypatch,
                     traffic=SHORT, bench=BENCH)
    assert r["correct"] is False, r["checks"]


def test_a_flipped_spike_in_I_is_not_correct(monkeypatch):
    faultkit._wrap_launches(monkeypatch, _flip_in_I)
    r = faultkit.run("recurrent.scan_t256", faultkit.SOUND, monkeypatch,
                     traffic=SHORT, bench=BENCH)
    assert r["correct"] is False
    kept = min(r["attempted"], simulate.KEPT)
    assert r["checks"]["spike_mismatches"]["value"] == kept


def test_a_reference_without_the_back_edge_lag_is_not_correct(monkeypatch):
    r = harness.run(
        "recurrent.scan_t256", 2**31 + 17, 1.0, False,
        t_process=time.perf_counter(), require_tpu=False,
        traffic_overrides=SHORT, bench=BENCH,
        tamper=_without_back_edge_lag(monkeypatch))
    assert r["correct"] is False
    assert r["checks"]["spike_mismatches"]["value"] > 0


def test_the_wide_fixture_compiles_and_runs_16_steps(monkeypatch, capsys):
    """The 8192 x 4096 CSR projection x2 -> out, over the program's dense
    cap, compiles serial and runs; the answers match the reference."""
    r = faultkit.run("recurrent_wide.scan_t256", faultkit.SOUND, monkeypatch,
                     traffic={"steps": 16}, bench=BENCH)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1
    notes = [json.loads(line) for line in capsys.readouterr().err.splitlines()
             if line.startswith("{")]
    setup = next(n for n in notes if n.get("stage") == "setup")
    assert setup["paradigms"][5] == "serial"
