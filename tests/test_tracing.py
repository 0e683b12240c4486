"""The program's host spans and counters (``repro.tracing``).

A launch marks its phases with ``tracing.span``; with no sink they cost a
global read and record nothing, with one installed they reach it in order,
each phase closed before the next opens, with the attributes an operator
reads (arrays read, bytes).  The scan's zero carry is traced into the
launch program (``carry.traced`` counts each trace), so a warm launch
builds none on the host.
"""
import contextlib

import numpy as np
import pytest

from repro import tracing
from repro.core import SwitchingCompiler, random_layer
from repro.core.layer import (
    LIFParams, Population, SNNNetwork, random_projection,
)
from repro.core.runtime import (
    lower_parallel, lower_serial, lowering_counts, network_executable,
)
from repro.core.switching import CompileReport

LIF = LIFParams(alpha=0.5, v_th=64.0)
STEPS, BATCH = 6, 2


class Recorder:
    """A sink that keeps (name, attrs, depth) in the order spans open and
    the names in the order they close."""

    def __init__(self):
        self.opened, self.closed, self._depth = [], [], 0

    @contextlib.contextmanager
    def __call__(self, name, **attrs):
        self.opened.append((name, attrs, self._depth))
        self._depth += 1
        try:
            yield
        finally:
            self._depth -= 1
            self.closed.append(name)


def _net(projections):
    """Populations of 12 / 10 / 6 neurons and the given (pre, post,
    paradigm) projections, compiled under those paradigms."""
    pops = {n: Population(f"tr.{n}", s)
            for n, s in (("in", 12), ("h", 10), ("out", 6))}
    projs = []
    for i, (pre, post, _par) in enumerate(projections):
        p = random_projection(pops[pre], pops[post], 0.4, 2, seed=70 + i)
        p.lif = LIF
        projs.append(p)
    net = SNNNetwork(populations=list(pops.values()), projections=projs)
    report = CompileReport(layers=[
        SwitchingCompiler(par).compile_layer(l)
        for (_, _, par), l in zip(projections, net.layers)
    ])
    spikes = (np.random.default_rng(3).random((STEPS, BATCH, net.n_input))
              < 0.4).astype(np.float32)
    return network_executable(net, report), spikes


CHAIN = [("in", "h", "parallel"), ("h", "out", "serial")]
LOOP = [("in", "h", "serial"), ("h", "h", "parallel"), ("h", "out", "serial")]


def _record(exe, spikes, **kw):
    exe.run(spikes, **kw)                      # compile outside the record
    rec = Recorder()
    with tracing.installed(rec):
        with tracing.span("outer"):
            exe.run(spikes, **kw)
    return rec


def test_no_sink_returns_one_shared_noop_and_records_nothing():
    off = tracing.span("launch.prepare", path="fused")
    assert tracing.span("launch.sync", what="outputs") is off
    with off as entered:
        assert entered is None
    exe, spikes = _net(CHAIN)
    rec = Recorder()
    with tracing.installed(rec):
        pass
    exe.run(spikes)
    assert rec.opened == [] and rec.closed == []


def test_scan_launch_records_its_phases_in_order():
    exe, spikes = _net(CHAIN)
    rec = _record(exe, spikes)
    names = [n for n, _, _ in rec.opened]
    assert names == ["outer", "launch.prepare", "launch.dispatch",
                     "launch.sync"]
    # every phase sits directly in the caller's span and closes before
    # the next opens
    assert [d for _, _, d in rec.opened] == [0, 1, 1, 1]
    assert rec.closed == names[1:] + ["outer"]
    attrs = {n: a for n, a, _ in rec.opened}
    assert attrs["launch.prepare"] == {"path": "fused", "batch": BATCH,
                                       "steps": STEPS}
    sync = attrs["launch.sync"]
    assert sync["what"] == "outputs"
    assert sync["arrays"] == len(exe.metas) == 2
    outs = exe.run(spikes)
    assert sync["bytes"] == sum(z.nbytes for z in outs)


@pytest.mark.parametrize("shape, kw", [
    (CHAIN, {}), (CHAIN, {"batched": True}),
    (LOOP, {}), (LOOP, {"temporal": True}),
], ids=["chain-fused", "chain-vmap", "loop-fused", "loop-temporal"])
def test_warm_launch_traces_no_carry(shape, kw):
    """The zero carry is traced once, into the new jit entry; a warm
    launch builds none, on the host or anywhere."""
    exe, spikes = _net(shape)
    before = tracing.counts().get("carry.traced", 0)
    exe.run(spikes, **kw)
    warm = tracing.counts().get("carry.traced", 0)
    assert warm > before
    rec = Recorder()
    with tracing.installed(rec):
        exe.run(spikes, **kw)
    assert tracing.counts().get("carry.traced", 0) == warm
    assert "launch.carry" not in [n for n, _, _ in rec.opened]
    assert "launch.dispatch" in [n for n, _, _ in rec.opened]


def test_vmap_launch_names_its_path():
    exe, spikes = _net(CHAIN)
    rec = _record(exe, spikes, batched=True)
    assert rec.opened[1] == ("launch.prepare",
                             {"path": "vmap", "batch": BATCH, "steps": STEPS},
                             1)


def test_temporal_launch_reads_passes_then_outputs():
    """A feed-forward temporal launch builds no carry; it reads the fixed
    point's pass counts and residual, then the outputs."""
    exe, spikes = _net(CHAIN)
    rec = _record(exe, spikes, temporal=True)
    names = [n for n, _, _ in rec.opened[1:]]
    assert names == ["launch.prepare", "launch.dispatch", "launch.sync",
                     "launch.sync"]
    syncs = [a for n, a, _ in rec.opened if n == "launch.sync"]
    assert [s["what"] for s in syncs] == ["passes", "outputs"]
    assert syncs[0]["arrays"] == 2
    assert sum(s["arrays"] for s in syncs) == 2 + len(exe.metas)
    assert rec.opened[1][1]["path"] == "temporal"


def test_temporal_launch_with_a_back_edge_matches_the_scans():
    """The back-edge block's zero state, made inside the temporal
    program, gives the fused and vmapped scans' bits."""
    exe, spikes = _net(LOOP)
    assert exe._temporal_structure().block
    got = exe.run(spikes, temporal=True)
    for base in (exe.run(spikes), exe.run(spikes, batched=True)):
        for a, b in zip(got, base):
            np.testing.assert_array_equal(a, b)
    assert sum(float(z.sum()) for z in got) > 0


def test_installed_restores_the_previous_sink_after_an_exception():
    outer, inner = Recorder(), Recorder()
    with tracing.installed(outer):
        with pytest.raises(RuntimeError):
            with tracing.installed(inner):
                with tracing.span("inside"):
                    raise RuntimeError("boom")
        with tracing.span("after"):
            pass
    assert [n for n, _, _ in inner.opened] == ["inside"]
    assert inner.closed == ["inside"]
    assert [n for n, _, _ in outer.opened] == ["after"]
    assert tracing.span("x") is tracing.span("y")


def test_counters_agree_with_lowering_counts():
    before, counted = lowering_counts(), tracing.counts()
    assert before == {"serial": counted.get("lower.serial", 0),
                      "parallel": counted.get("lower.parallel", 0)}
    layer = random_layer(10, 8, density=0.5, delay_range=2, seed=5)
    lower_serial(SwitchingCompiler("serial").compile_layer(layer).program)
    lower_parallel(SwitchingCompiler("parallel").compile_layer(layer).program)
    lower_parallel(SwitchingCompiler("parallel").compile_layer(layer).program)
    after = lowering_counts()
    assert after == {"serial": before["serial"] + 1,
                     "parallel": before["parallel"] + 2}
    tracing.count("test.tracing", 3)
    assert tracing.counts()["test.tracing"] - counted.get("test.tracing", 0) == 3
    assert tracing.counts()["lower.serial"] == after["serial"]
