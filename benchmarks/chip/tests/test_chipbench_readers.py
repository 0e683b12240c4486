"""A roofline share is read only from a trace that holds every call the
traced launches made."""
import types

import pytest

from chipbench import readers, trace, work
from chipbench.spans import Span

PEAKS = work.peaks_for("TPU v5 lite")
PER_CALL = work.Work(197e6, 1.0)          # 1 us at the bf16 peak


def ctx(kernel_ops):
    """Two launches of 2 calls each in the window, then one that overran
    it; the trace holds all three."""
    host = [("window", 0.0, 10.0), ("sim.launch", 0.0, 4.0),
            ("sim.launch", 4.0, 8.0), ("sim.launch", 8.0, 9.9)]
    r = trace.Reduced(
        window=(0.0, 10.0),
        ops={"/device:TPU:0": [trace.Op(n, a, b) for n, a, b in kernel_ops]},
        host=[trace.Op(n, a, b) for n, a, b in host])
    ran = [Span("sim.launch", a, b, {}) for _, a, b in host[1:]]
    return types.SimpleNamespace(trace=r, launches=ran[:2], ran=ran,
                                 peaks=PEAKS)


def two_calls(_span):
    return 2, PER_CALL * 2


# 2 us each; the overrunning launch's last call ends past the window, and
# the device clock may run ahead of the host's
CALLS = [("k.1", 1.0, 1.000002), ("k.2", 2.0, 2.000002),
         ("k.1", 3.99999, 3.999992), ("k.2", 6.0, 6.000002),
         ("k.1", 9.0, 9.000002), ("k.2", 10.5, 10.500002)]


def test_a_whole_trace_gives_the_share():
    share = readers.roofline(ctx(CALLS), "k", two_calls)
    assert share == pytest.approx(50.0)


@pytest.mark.parametrize("ops", [CALLS[:5], CALLS + [("k.3", 7.0, 7.1)], []])
def test_a_trace_with_other_calls_than_the_launches_made_gives_none(ops):
    assert readers.roofline(ctx(ops), "k", two_calls) is None


def test_a_trace_that_lost_a_launch_span_gives_none():
    c = ctx(CALLS)
    c.trace.host = c.trace.host[:-1]
    assert readers.roofline(c, "k", two_calls) is None


def test_no_trace_gives_none():
    c = ctx(CALLS)
    c.trace = None
    assert readers.roofline(c, "k", two_calls) is None
