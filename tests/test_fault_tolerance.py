"""Unit coverage for the distributed fault-tolerance primitives.

These are the pure-bookkeeping pieces the serving supervisor wires to
real signals (`HeartbeatRegistry`, `StragglerDetector`, `RestartPolicy`),
all injectable-clock / pure-function, so they test deterministically on
one host.
"""
import pytest

from repro.distributed.fault_tolerance import (
    HeartbeatRegistry,
    RestartPolicy,
    StragglerDetector,
)


# -- heartbeats --------------------------------------------------------------

def test_heartbeat_registry_tracks_and_declares_dead():
    reg = HeartbeatRegistry(timeout_s=10.0)
    reg.beat(0, now=100.0)
    reg.beat(1, now=100.0)
    assert reg.dead_hosts(now=105.0) == []
    reg.beat(0, now=109.0)              # host 0 keeps beating
    assert reg.dead_hosts(now=112.0) == [1]
    reg.beat(1, now=113.0)              # a dead host may come back
    assert reg.dead_hosts(now=114.0) == []


def test_heartbeat_age():
    reg = HeartbeatRegistry(timeout_s=10.0)
    assert reg.age(7) is None           # never seen
    reg.beat(7, now=50.0)
    assert reg.age(7, now=53.5) == pytest.approx(3.5)
    reg.beat(7, now=60.0)               # age resets on every beat
    assert reg.age(7, now=60.0) == pytest.approx(0.0)


# -- stragglers --------------------------------------------------------------

def test_straggler_detector_needs_two_hosts():
    det = StragglerDetector(threshold=1.5)
    det.record(0, 1.0)
    assert det.stragglers() == []       # one host has no fleet median


def test_straggler_detector_flags_beyond_threshold():
    det = StragglerDetector(threshold=1.5, ema=0.0)   # ema=0: latest wins
    for h in range(4):
        det.record(h, 1.0)
    det.record(3, 2.0)                  # 2.0 > 1.5 x median(1.0)
    assert det.stragglers() == [3]
    det.record(3, 1.0)                  # recovers once its time drops
    assert det.stragglers() == []


def test_straggler_detector_ema_smooths_single_spike():
    det = StragglerDetector(threshold=1.5, ema=0.9)
    for _ in range(10):
        for h in range(3):
            det.record(h, 1.0)
    det.record(1, 3.0)                  # one spike, EMA absorbs most of it
    assert det.stragglers() == []
    for _ in range(20):                 # a persistent drift does flag
        det.record(1, 3.0)
    assert det.stragglers() == [1]


# -- restart policy ----------------------------------------------------------

def test_restart_policy_exponential_backoff():
    pol = RestartPolicy(max_retries=3, backoff_s=0.5)
    assert [pol.next_delay(a) for a in range(4)] == [0.5, 1.0, 2.0, 4.0]


def test_restart_policy_retry_budget_boundary():
    pol = RestartPolicy(max_retries=2)
    assert pol.should_restart(0) and pol.should_restart(1)
    assert not pol.should_restart(2)    # attempt == max_retries: stop
