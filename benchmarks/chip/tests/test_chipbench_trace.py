"""The trace reduction: busy union, idle share, kernel time by name and
the breakdown, on hand-made intervals and on a trace recorded on a v5e."""
from pathlib import Path

import pytest

from chipbench import trace

FIXTURE = Path(__file__).resolve().parent / "data" / "scan_t256.xplane.pb"


def reduced(ops, host=(), window=(0.0, 10.0)):
    return trace.Reduced(
        window=window,
        ops={"/device:TPU:0": [trace.Op(n, a, b) for n, a, b in ops]},
        host=[trace.Op(n, a, b) for n, a, b in host])


def test_union_merges_overlaps_and_touching():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]


def test_busy_is_the_union_clipped_to_the_window():
    r = reduced([("a", -1.0, 1.0), ("b", 0.5, 2.0), ("c", 9.0, 12.0)])
    assert r.busy_s == pytest.approx(3.0)
    assert r.window_s == 10.0


def test_kernel_time_by_name_inside_the_window():
    r = reduced([("_lif_kernel.1", 1.0, 1.5), ("_lif_kernel.2", 2.0, 2.25),
                 ("fusion.3", 3.0, 4.0), ("_lif_kernel.9", 11.0, 12.0)])
    assert r.kernel("_lif_kernel") == (pytest.approx(0.75), 2)
    assert r.kernel("_scan_kernel") == (0.0, 0)


def test_gaps_are_named_by_the_innermost_host_span():
    r = reduced([("op", 0.0, 2.0), ("op", 6.0, 10.0)],
                host=[("sim.launch", 0.0, 9.0), ("sim.check", 1.5, 6.5),
                      ("client.submit", 2.0, 2.5)])
    assert r.gaps() == [(2.0, 6.0)]
    b = r.breakdown()
    assert b["idle_gaps"] == [["sim.check", 4.0]]
    assert b["device_ops"] == [["op", 6.0]]


def test_idle_window_with_no_device_plane():
    r = trace.Reduced(window=(0.0, 1.0), ops={}, host=[])
    assert r.busy_s == 0.0 and r.gaps() == [(0.0, 1.0)]


def test_trace_recorded_on_a_v5e():
    """A 0.02 s traced window of ``gesture.scan_t256``: three launches of
    256 steps at batch 8 ran in it (two completed inside it), each step
    one ``spike_wdm_matmul`` (layer 1) and one ``lif_update`` per
    population."""
    r = trace.reduce(FIXTURE, "window")
    assert list(r.ops) == ["/device:TPU:0"]
    assert 0.0 < r.busy_s <= r.window_s
    assert len(r.host_spans("sim.launch")) == 3
    seconds, calls = r.kernel("lif_update_pallas", whole=True)
    assert calls == 3 * 256 * 2 and 0.0 < seconds < r.busy_s
    assert r.kernel("spike_wdm_matmul_pallas", whole=True)[1] == 3 * 256
    assert r.kernel("lif_update") == (0.0, 0)
    assert r.kernel("affine_scan_pallas") == (0.0, 0)
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= trace.TOP
    assert 0 < len(b["idle_gaps"]) <= trace.TOP


def test_kernel_calls_in_the_whole_trace():
    r = reduced([("k.1", 1.0, 1.5), ("k.2", 3.0, 3.5), ("k.3", 11.0, 11.5)])
    assert r.kernel("k") == (pytest.approx(1.0), 2)
    assert r.kernel("k", whole=True) == (pytest.approx(1.5), 3)


def test_host_spans_inside_the_window_in_start_order():
    r = reduced([], host=[("sim.launch", 5.0, 6.0), ("sim.launch", 1.0, 2.0),
                          ("sim.launch", 9.5, 10.5), ("other", 0.0, 1.0)])
    assert [(o.t0, o.t1) for o in r.host_spans("sim.launch")] == [
        (1.0, 2.0), (5.0, 6.0)]
