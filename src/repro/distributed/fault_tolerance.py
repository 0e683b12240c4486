"""Fault-tolerance primitives: heartbeats, straggler detection, restart policy.

The serving stack's launch supervisor
(:class:`repro.serving.supervisor.LaunchSupervisor`) beats the
:class:`HeartbeatRegistry` from the continuous-serving loop and every
completed launch, feeds per-``(model, bucket)`` launch wall-times into
the :class:`StragglerDetector` as its launch-stall signal, and drives
its retry backoff from :class:`RestartPolicy`.  The module is pure
bookkeeping with injectable clocks, so it unit-tests on one host.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional


@dataclasses.dataclass
class HostState:
    host_id: int
    last_heartbeat: float
    step_time_ema: Optional[float] = None
    steps: int = 0


class HeartbeatRegistry:
    def __init__(self, timeout_s: float = 60.0):
        self.timeout_s = timeout_s
        self.hosts: Dict[int, HostState] = {}

    def beat(self, host_id: int, now: Optional[float] = None) -> None:
        now = time.monotonic() if now is None else now
        st = self.hosts.get(host_id)
        if st is None:
            self.hosts[host_id] = HostState(host_id, now)
        else:
            st.last_heartbeat = now

    def dead_hosts(self, now: Optional[float] = None) -> List[int]:
        now = time.monotonic() if now is None else now
        return [
            h for h, st in self.hosts.items()
            if now - st.last_heartbeat > self.timeout_s
        ]

    def age(
        self, host_id: int, now: Optional[float] = None
    ) -> Optional[float]:
        """Seconds since ``host_id`` last beat; ``None`` if never seen."""
        st = self.hosts.get(host_id)
        if st is None:
            return None
        now = time.monotonic() if now is None else now
        return now - st.last_heartbeat


class StragglerDetector:
    """Flags hosts whose step time exceeds threshold x fleet median."""

    def __init__(self, threshold: float = 1.5, ema: float = 0.9):
        self.threshold = threshold
        self.ema = ema
        self.times: Dict[int, float] = {}

    def record(self, host_id: int, step_seconds: float) -> None:
        prev = self.times.get(host_id)
        self.times[host_id] = (
            step_seconds if prev is None
            else self.ema * prev + (1 - self.ema) * step_seconds
        )

    def stragglers(self) -> List[int]:
        if len(self.times) < 2:
            return []
        vals = sorted(self.times.values())
        median = vals[len(vals) // 2]
        return [
            h for h, t in self.times.items() if t > self.threshold * median
        ]


@dataclasses.dataclass
class RestartPolicy:
    max_retries: int = 3
    backoff_s: float = 5.0

    def next_delay(self, attempt: int) -> float:
        return self.backoff_s * (2 ** attempt)

    def should_restart(self, attempt: int) -> bool:
        return attempt < self.max_retries
