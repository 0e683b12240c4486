"""The program's launch phases read from a trace: per launch, the device
clock's offset, idle gaps named on the host's clock, and traced runs of
both cells on the CPU with the program's spans recorded."""
import pytest

import launch_phases
from chipbench import phases, trace

MS = 1e-3


def ev(name, a, b, **attrs):
    return phases.Event(name, a * MS, b * MS, attrs)


def launch(a, b):
    return trace.Op("sim.launch", a * MS, b * MS)


#: Two launches on the host's clock (ms).  The first builds a carry and
#: waits 0.8 ms for its device program; the second's program starts as it
#: is dispatched.
EVENTS = [ev("launch.prepare", 0.1, 0.5), ev("launch.carry", 0.5, 5.0, arrays=9),
          ev("launch.dispatch", 5.0, 5.8),
          ev("launch.sync", 5.8, 9.8, what="outputs", arrays=2),
          ev("launch.prepare", 10.1, 10.5), ev("launch.dispatch", 11.0, 11.2),
          ev("launch.sync", 11.2, 12.0, what="passes", arrays=2),
          ev("launch.sync", 12.0, 13.5, what="outputs", arrays=2)]
LAUNCHES = [launch(0.0, 10.0), launch(10.0, 14.0)]


def test_phases_per_launch():
    rows = phases.per_launch(LAUNCHES, EVENTS)
    assert rows[0]["seconds"] == pytest.approx(
        {"launch.prepare": 0.4 * MS, "launch.carry": 4.5 * MS,
         "launch.dispatch": 0.8 * MS, "launch.sync": 4.0 * MS})
    assert rows[1]["seconds"]["launch.sync"] == pytest.approx(2.3 * MS)
    assert "launch.carry" not in rows[1]["seconds"]
    assert [r["arrays"] for r in rows] == [2, 4]
    assert [r["launch_s"] for r in rows] == pytest.approx([10 * MS, 4 * MS])
    s = phases.summary(rows)
    assert s["host_ms.carry"] == pytest.approx(2.25)
    assert s["host_ms.dispatch"] == pytest.approx(0.5)
    assert s["host_ms.sync"] == pytest.approx(3.15)
    assert s["d2h_reads.sim"] == 3
    assert s["covered"] == pytest.approx((9.7 + 2.9) / 14.0)


def test_launches_without_a_carry_read_none():
    no_carry = [e for e in EVENTS if e.name != "launch.carry"]
    s = phases.summary(phases.per_launch(LAUNCHES, no_carry))
    assert s["host_ms.carry"] is None
    assert s["host_ms.dispatch"] == pytest.approx(0.5)
    assert phases.summary([]) == {}


def device(shift_ms):
    """The device's ops as its clock reads them, ``shift_ms`` early: the
    carry's small programs, then each launch's entry."""
    host_ms = [("broadcast_in_dim", 1.0, 1.01), ("broadcast_in_dim", 4.99, 5.0),
               ("jit_checked(1)", 5.8, 8.75),
               ("broadcast_in_dim", 10.6, 10.61), ("jit_checked(1)", 11.0, 13.0)]
    return [trace.Op(n, (a - shift_ms) * MS, (b - shift_ms) * MS)
            for n, a, b in host_ms]


def reduced(ops):
    return trace.Reduced(window=(0.0, 14.0 * MS), ops={"/device:TPU:0": ops},
                         host=list(LAUNCHES))


def test_clock_offset_is_the_least_dispatch_to_module_start():
    assert phases.clock_offset(EVENTS, device(0.75)) == pytest.approx(-0.75 * MS)
    assert phases.clock_offset(EVENTS, device(0.0)) == pytest.approx(0.0)


def test_offset_moves_a_gap_from_the_carry_to_the_dispatch():
    """The device reads 0.75 ms early: the 0.8 ms the first launch waits
    for its program, read raw, lies mostly in the carry."""
    ops = device(0.75)
    r = reduced(ops)
    offset = phases.clock_offset(EVENTS, ops)

    def name_of_wait(gaps):
        return [n for n, s in gaps if s == pytest.approx(0.8 * MS)]

    assert name_of_wait(phases.idle_gaps(r, EVENTS, None)) == ["launch.carry"]
    assert name_of_wait(phases.idle_gaps(r, EVENTS, offset)) == ["launch.dispatch"]


def test_unpaired_dispatches_give_no_offset_and_no_shift():
    ops = device(0.75)[:-1]
    assert phases.clock_offset(EVENTS, ops) is None
    assert phases.clock_offset([], ops) is None
    r = reduced(ops)
    assert (phases.idle_gaps(r, EVENTS, None)
            == phases.idle_gaps(r, EVENTS, phases.clock_offset(EVENTS, ops)))


@pytest.mark.parametrize("cell, carry, reads", [
    ("gesture.scan_t256", True, 2), ("gesture.window_t256", False, 4)])
def test_traced_run_on_the_cpu_reads_every_phase(cell, carry, reads):
    """One traced run at 32 steps a launch: every launch records its phases
    in the trace.  The scan builds a carry and reads its two outputs; the
    temporal path builds none and reads pass counts and residual first.
    The CPU has no device plane, so no offset pairs and the gaps are named
    unshifted."""
    out = launch_phases.measure(cell, 2**31 + 13, require_tpu=False,
                                traffic_overrides={"steps": 32})
    assert out["correct"] is True
    assert out["traced_launches"] >= 1
    for name in ("host_ms.prepare", "host_ms.dispatch", "host_ms.sync"):
        assert out[name] > 0.0, name
    assert (out["host_ms.carry"] is not None) is carry
    assert out["d2h_reads.sim"] == reads
    assert 0.0 < out["covered"] <= 1.0
    assert out["clock_offset_s"] is None
    assert out["idle_gaps"] == out["idle_gaps_unshifted"]
