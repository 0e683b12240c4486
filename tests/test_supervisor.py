"""Launch-supervisor units: breaker lifecycle, retry/degrade/bisect logic,
output validation, payload validation, shutdown-reply regression.

The supervisor is exercised here against a scripted stub pool with an
injectable clock, so every path — watchdog stall, transient fault,
path degradation, poison-request bisection, breaker trip/probe — is
deterministic and fast.  End-to-end behavior against the real engine
and compiled executables lives in ``test_chaos.py``.
"""
import asyncio

import numpy as np
import pytest

from repro.core import SwitchingCompiler, random_layer
from repro.core.layer import LIFParams, SNNNetwork
from repro.core.runtime import OutputValidationError, validate_spike_outputs
from repro.distributed.fault_tolerance import RestartPolicy
from repro.serving import (
    BucketKey,
    CircuitBreaker,
    FailedReply,
    LaunchSupervisor,
    RequestQueue,
    ServingEngine,
    ShutdownReply,
    SNNRequest,
    pad_microbatch,
)
from repro.core.switching import CompileReport


# -- scripted fixtures -------------------------------------------------------

class Clock:
    """Injectable monotonic clock the stub pool can advance mid-launch."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class _Entry:
    def __init__(self, sizes):
        self.output_sizes = sizes


class StubPool:
    """Scripted ExecutablePool stand-in.

    ``fail`` maps ``path -> remaining failure count`` (-1 = persistent);
    ``poison`` is a request id whose presence makes any launch raise;
    ``launch_cost_s`` advances the injected clock per launch (the
    watchdog's elapsed-time signal).
    """

    def __init__(self, clock, sizes=(5,), full_bucket_path="batched"):
        self.clock = clock
        self.sizes = sizes
        self.full_bucket_path = full_bucket_path
        self.fail = {}
        self.poison = None
        self.launch_cost_s = 0.001
        self.launches = []

    def peek(self, name):
        return _Entry(self.sizes)

    def run_microbatch(self, mb, *, path=None, block=True):
        self.launches.append((path, tuple(r.request_id for r in mb.requests)))
        self.clock.advance(self.launch_cost_s)
        if self.poison is not None and any(
            r.request_id == self.poison for r in mb.requests
        ):
            raise RuntimeError("poison request aboard")
        left = self.fail.get(path, 0)
        if left:
            if left > 0:
                self.fail[path] = left - 1
            raise RuntimeError(f"scripted {path} failure")
        return [
            np.zeros((mb.key.steps, mb.key.batch, n), np.float32)
            for n in self.sizes
        ]


def make_mb(n_requests, key=None, model="default"):
    key = key or BucketKey(steps=8, n_in=4, batch=4)
    reqs = [
        SNNRequest(
            request_id=i,
            spikes=np.zeros((4, key.n_in), np.float32),
            t_enqueue=0.0,
        )
        for i in range(n_requests)
    ]
    return pad_microbatch(key, reqs, model)


def make_supervisor(pool, clock, **kw):
    kw.setdefault("policy", RestartPolicy(max_retries=2, backoff_s=0.0))
    return LaunchSupervisor(pool, clock=clock, **kw)


# -- circuit breaker ---------------------------------------------------------

def test_breaker_trips_after_consecutive_failures():
    clk = Clock()
    br = CircuitBreaker(threshold=3, cooldown_s=1.0, clock=clk)
    for _ in range(2):
        br.record_failure()
    assert br.state == "closed" and br.allow()
    br.record_success()                 # success resets the streak
    for _ in range(3):
        br.record_failure()
    assert br.state == "open" and br.trips == 1
    assert not br.allow()               # cooldown not elapsed


def test_breaker_half_open_probe_closes_or_reopens():
    clk = Clock()
    br = CircuitBreaker(threshold=1, cooldown_s=1.0, clock=clk)
    br.record_failure()
    assert br.state == "open"
    clk.advance(1.5)
    assert br.allow()                   # the half-open probe
    assert br.state == "half_open" and br.probes == 1
    br.record_failure()                 # failed probe: re-open, new cooldown
    assert br.state == "open" and not br.allow()
    clk.advance(1.5)
    assert br.allow() and br.probes == 2
    br.record_success()
    assert br.state == "closed" and br.allow()
    assert br.trips == 1                # re-opening a probe is not a new trip


# -- output validation guard -------------------------------------------------

def good_outs(steps=8, batch=4, sizes=(5, 3)):
    return [np.zeros((steps, batch, n), np.float32) for n in sizes]


def test_validate_accepts_clean_binary_trains():
    outs = good_outs()
    outs[0][1, 2, 3] = 1.0
    validate_spike_outputs(outs, steps=8, batch=4, sizes=(5, 3))
    validate_spike_outputs(outs, steps=8, batch=4)   # sizes optional


@pytest.mark.parametrize("bad,match", [
    (np.nan, "non-finite"),
    (np.inf, "non-finite"),
    (2.0, "non-binary"),
    (0.5, "non-binary"),
])
def test_validate_rejects_corrupt_entries(bad, match):
    outs = good_outs()
    outs[1][0, 0, 0] = bad
    with pytest.raises(OutputValidationError, match=match):
        validate_spike_outputs(outs, steps=8, batch=4, sizes=(5, 3))


def test_validate_rejects_contract_violations():
    with pytest.raises(OutputValidationError, match="expected 2"):
        validate_spike_outputs(good_outs()[:1], steps=8, batch=4,
                               sizes=(5, 3))
    with pytest.raises(OutputValidationError, match="shape"):
        validate_spike_outputs(good_outs(steps=7), steps=8, batch=4,
                               sizes=(5, 3))
    wrong_dtype = [z.astype(np.float64) for z in good_outs()]
    with pytest.raises(OutputValidationError, match="float32"):
        validate_spike_outputs(wrong_dtype, steps=8, batch=4, sizes=(5, 3))


@pytest.mark.parametrize("corrupt,match", [
    (lambda o: [o[0][:, :, 0], o[1]], "shape"),          # a 2-D train
    (lambda o: [o[0][:, :3], o[1]], "shape"),             # wrong batch
    (lambda o: [o[0], o[1][:, :, :2]], "shape"),          # wrong width
    (lambda o: [o[0], _poke(o[1], -1.0)], "projection 1: non-binary"),
    (lambda o: [_poke(o[0], -np.inf), o[1]], "projection 0: non-finite"),
], ids=["2-D", "batch", "width", "negative", "minus-inf"])
def test_validate_rejects_each_remaining_signature(corrupt, match):
    with pytest.raises(OutputValidationError, match=match):
        validate_spike_outputs(corrupt(good_outs()), steps=8, batch=4,
                               sizes=(5, 3))


def _poke(z, value):
    z = z.copy()
    z[3, 1, 2] = value
    return z


# -- supervised launch paths -------------------------------------------------

def test_fault_free_launch_single_attempt_trims_replies():
    clk = Clock()
    pool = StubPool(clk)
    sup = make_supervisor(pool, clk)
    mb = make_mb(4)                     # full bucket -> batched path
    replies = sup.run(mb)
    assert set(replies) == {0, 1, 2, 3}
    for rid, trains in replies.items():
        assert [z.shape for z in trains] == [(4, 5)]   # trimmed to true steps
    assert sup.counters["launch_attempts"] == 1
    assert sup.counters["retries"] == 0
    assert pool.launches[0][0] == "batched"


def test_transient_fault_retried_on_same_path():
    clk = Clock()
    pool = StubPool(clk)
    pool.fail["batched"] = 2            # two transient failures, then clean
    sup = make_supervisor(pool, clk)
    replies = sup.run(make_mb(4))
    assert all(not isinstance(r, FailedReply) for r in replies.values())
    assert sup.counters["retries"] == 2
    assert sup.counters["degraded_launches"] == 0
    assert [p for p, _ in pool.launches] == ["batched"] * 3


def test_persistent_path_fault_degrades_to_alternate_path():
    clk = Clock()
    pool = StubPool(clk)
    pool.fail["batched"] = -1           # batched path never works
    sup = make_supervisor(pool, clk)
    replies = sup.run(make_mb(4))
    assert all(not isinstance(r, FailedReply) for r in replies.values())
    assert sup.counters["degraded_launches"] == 1
    assert pool.launches[-1][0] == "fused"


def test_partial_bucket_defaults_to_fused_then_batched():
    clk = Clock()
    pool = StubPool(clk)
    pool.fail["fused"] = -1
    sup = make_supervisor(pool, clk)
    replies = sup.run(make_mb(2))       # 2 of 4 slots -> fused default
    assert all(not isinstance(r, FailedReply) for r in replies.values())
    assert pool.launches[0][0] == "fused"
    assert pool.launches[-1][0] == "batched"
    assert sup.counters["degraded_launches"] == 1


def test_watchdog_discards_stalled_launch_and_retries():
    clk = Clock()
    pool = StubPool(clk)
    pool.launch_cost_s = 0.2            # first launches stall past budget
    sup = make_supervisor(pool, clk, watchdog_s=0.1)

    launches = {"n": 0}
    orig = pool.run_microbatch

    def run(mb, *, path=None, block=True):
        launches["n"] += 1
        if launches["n"] == 2:
            pool.launch_cost_s = 0.01   # second attempt is healthy
        return orig(mb, path=path, block=block)

    pool.run_microbatch = run
    replies = sup.run(make_mb(4))
    assert all(not isinstance(r, FailedReply) for r in replies.values())
    assert sup.counters["watchdog_stalls"] == 1
    assert sup.counters["retries"] == 1


def test_validation_failure_counts_and_retries():
    clk = Clock()
    pool = StubPool(clk)
    corrupt = {"left": 1}
    orig = pool.run_microbatch

    def run(mb, *, path=None, block=True):
        outs = orig(mb, path=path, block=block)
        if corrupt["left"]:
            corrupt["left"] -= 1
            outs[0] = outs[0].copy()
            outs[0][0, 0, 0] = np.nan
        return outs

    pool.run_microbatch = run
    sup = make_supervisor(pool, clk)
    replies = sup.run(make_mb(4))
    assert all(not isinstance(r, FailedReply) for r in replies.values())
    assert sup.counters["validation_failures"] == 1
    assert sup.counters["retries"] == 1


def test_bisection_quarantines_only_the_poison_request():
    clk = Clock()
    pool = StubPool(clk)
    pool.poison = 2                     # any batch carrying rid 2 fails
    sup = make_supervisor(pool, clk)
    mb = make_mb(4)
    replies = sup.run(mb)
    assert set(replies) == {0, 1, 2, 3}     # exactly one reply per request
    assert isinstance(replies[2], FailedReply)
    assert replies[2].fault_kind == "error"
    assert not replies[2]                   # falsy, like ShedReply
    for rid in (0, 1, 3):
        assert not isinstance(replies[rid], FailedReply)
    assert sup.counters["bisections"] == 1
    assert sup.counters["quarantined"] == 1


def test_whole_batch_persistent_failure_fails_every_request():
    clk = Clock()
    pool = StubPool(clk)
    pool.fail["batched"] = -1
    pool.fail["fused"] = -1
    sup = make_supervisor(pool, clk)
    replies = sup.run(make_mb(3))
    assert set(replies) == {0, 1, 2}
    assert all(isinstance(r, FailedReply) for r in replies.values())
    assert sup.counters["quarantined"] == 3


def test_breaker_skips_open_path_and_probe_recovers():
    clk = Clock()
    pool = StubPool(clk)
    pool.fail["batched"] = -1
    sup = make_supervisor(
        pool, clk, breaker_threshold=2, breaker_cooldown_s=10.0,
        policy=RestartPolicy(max_retries=0, backoff_s=0.0),
    )
    sup.run(make_mb(4))                 # failure 1 on batched
    sup.run(make_mb(4))                 # failure 2 -> breaker opens
    stats = sup.stats()
    assert stats["breaker_trips"] == 1 and stats["open_breakers"] == 1
    pool.launches.clear()
    sup.run(make_mb(4))                 # open: batched never attempted
    assert [p for p, _ in pool.launches] == ["fused"]
    assert sup.counters["breaker_skips"] == 1
    pool.fail.pop("batched")            # path heals
    clk.advance(11.0)                   # cooldown elapses
    pool.launches.clear()
    sup.run(make_mb(4))                 # half-open probe on batched succeeds
    stats = sup.stats()
    assert pool.launches[0][0] == "batched"
    assert stats["breaker_probes"] == 1 and stats["open_breakers"] == 0
    assert "open" not in stats["breakers"].values()


def test_heartbeats_and_stragglers_surface_in_stats():
    clk = Clock()
    pool = StubPool(clk)
    sup = make_supervisor(pool, clk, straggler_threshold=2.0)
    sup.beat_loop()
    sup.run(make_mb(4))
    st = sup.stats()
    assert st["launch_heartbeat_age_s"] is not None
    assert st["loop_heartbeat_age_s"] is not None
    assert st["dead_hosts"] == []
    # three bucket shapes; the one whose launches run persistently slow
    # flags against the fleet median of the other two
    sup.run(make_mb(4, key=BucketKey(steps=32, n_in=4, batch=4)))
    slow_key = BucketKey(steps=16, n_in=4, batch=4)
    pool.launch_cost_s = 0.1
    for _ in range(30):
        sup.run(make_mb(4, key=slow_key))
    assert sup.counters["straggler_flags"] > 0
    assert any("16x4x4" in s for s in sup.stats()["stragglers"])


# -- payload validation at submit (front-door guard) -------------------------

def test_submit_rejects_faulty_payloads():
    q = RequestQueue()
    with pytest.raises(ValueError, match="non-finite"):
        q.submit(np.array([[1.0, np.nan], [0.0, 0.0]]))
    with pytest.raises(ValueError, match="non-finite"):
        q.submit(np.array([[np.inf, 0.0]]))
    with pytest.raises(ValueError, match="binary"):
        q.submit(np.array([[0.0, 0.5]]))
    with pytest.raises(ValueError, match="dtype"):
        q.submit(np.array([["a", "b"]]))
    with pytest.raises(ValueError, match=r"\(steps, n_in\)"):
        q.submit(np.ones((3,), np.float32))
    with pytest.raises(ValueError, match=r"\(steps, n_in\)"):
        q.submit(np.ones((3, 2, 2), np.float32))


def test_submit_accepts_binary_in_any_numeric_dtype():
    q = RequestQueue()
    for dtype in (np.float32, np.float64, np.int64, np.uint8, bool):
        req = q.submit(np.array([[0, 1], [1, 0]], dtype=dtype))
        assert req.spikes.dtype == np.float32
        assert set(np.unique(req.spikes)) <= {0.0, 1.0}


# -- shutdown resolves pending futures (regression) --------------------------

def _tiny_engine():
    rng = np.random.default_rng(0)
    lay = random_layer(4, 3, density=0.5, delay_range=2,
                       seed=int(rng.integers(0, 2**31)))
    lay.lif = LIFParams(alpha=0.5, v_th=64.0)
    net = SNNNetwork(layers=[lay])
    report = CompileReport(
        layers=[SwitchingCompiler("serial").compile_layer(lay)]
    )
    return ServingEngine(net, report, micro_batch=2)


def test_stop_resolves_pending_async_futures_with_shutdown_reply():
    eng = _tiny_engine()
    spikes = np.zeros((4, 4), np.float32)

    async def main():
        # two waiters, never served: no serve loop is running
        t1 = asyncio.create_task(eng.submit_async(spikes))
        t2 = asyncio.create_task(eng.submit_async(spikes, priority=1))
        await asyncio.sleep(0)          # let both register their futures
        assert len(eng._futures) == 2
        eng.stop()
        r1 = await asyncio.wait_for(t1, timeout=2.0)
        r2 = await asyncio.wait_for(t2, timeout=2.0)
        return r1, r2

    r1, r2 = asyncio.run(main())
    for r in (r1, r2):
        assert isinstance(r, ShutdownReply)
        assert not r                    # falsy non-result, like ShedReply
    assert eng._futures == {}


def test_stop_is_idempotent_without_waiters():
    eng = _tiny_engine()
    eng.stop()
    eng.stop()
    assert eng._futures == {}
