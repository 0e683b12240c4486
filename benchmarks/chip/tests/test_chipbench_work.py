"""Operation and byte counts against hand counts; the peaks table."""
import json

import pytest

from chipbench import work

V5E = "TPU v5 lite"


def test_lif_update_counts():
    w = work.lif_update(20, 8)
    assert (w.ops, w.bytes, w.int8) == (800.0, 3200.0, False)


def test_spike_wdm_matmul_counts():
    w = work.spike_wdm_matmul(20, 64, 8)
    # 20x64 int8 weights + 64x8 int8 spikes + 20x8 int32 currents
    assert (w.ops, w.bytes, w.int8) == (20480.0, 1280 + 512 + 640, True)


def test_lif_parallel_scan_counts():
    w = work.lif_parallel_scan(256, 160)
    assert (w.ops, w.bytes) == (2.0 * 256 * 160, 8.0 * 256 * 160)


def test_network_step_counts():
    w = work.network_step(n_synapses=100, n_neurons=10, n_input=30, batch=2)
    assert w.ops == 2 * 100 * 2 + 5 * 10 * 2
    # table once; 10 updated neurons x 10 bytes and 30 input spikes x 1
    # byte, per lane
    assert w.bytes == 6 * 100 + 10 * 10 * 2 + 30 * 2


def test_work_arithmetic_keeps_int8_apart():
    a = work.spike_wdm_matmul(2, 2, 2)
    assert (a * 3).ops == 3 * a.ops
    with pytest.raises(ValueError):
        a + work.lif_update(2, 2)


def test_least_time_names_the_binding_bound():
    peaks = work.peaks_for(V5E)
    assert work.least_time(work.Work(197e12, 1.0), peaks) == (1.0, "compute")
    t, bound = work.least_time(work.Work(1.0, 819e9), peaks)
    assert bound == "bandwidth" and t == pytest.approx(1.0)
    t, bound = work.least_time(work.Work(393e12, 1.0, int8=True), peaks)
    assert bound == "compute" and t == pytest.approx(1.0)


def test_peaks_keyed_by_device_kind_with_source():
    table = json.loads(work._PEAKS.read_text())
    assert "Google Cloud" in table["source"]
    assert work.peaks_for(V5E)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks_for("cpu")
