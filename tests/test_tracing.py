"""The program's host spans and counters (``repro.tracing``).

A launch marks its phases with ``tracing.span``; with no sink they cost a
global read and record nothing, with one installed they reach it in order,
each phase closed before the next opens, with the attributes an operator
reads (arrays made or read, bytes).
"""
import contextlib

import jax
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
from repro.core.runtime.executor import _carry_arrays, _init_graph_carry
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
    assert names == ["outer", "launch.prepare", "launch.carry",
                     "launch.dispatch", "launch.sync"]
    # every phase sits directly in the caller's span and closes before
    # the next opens
    assert [d for _, _, d in rec.opened] == [0, 1, 1, 1, 1]
    assert rec.closed == names[1:] + ["outer"]
    attrs = {n: a for n, a, _ in rec.opened}
    assert attrs["launch.prepare"] == {"path": "fused", "batch": BATCH,
                                       "steps": STEPS}
    sync = attrs["launch.sync"]
    assert sync["what"] == "outputs"
    assert sync["arrays"] == len(exe.metas) == 2
    outs = exe.run(spikes)
    assert sync["bytes"] == sum(z.nbytes for z in outs)


@pytest.mark.parametrize("shape", [CHAIN, LOOP], ids=["chain", "loop"])
def test_carry_arrays_count_the_leaves_made(shape):
    exe, _ = _net(shape)
    leaves = jax.tree_util.tree_leaves(
        _init_graph_carry(exe.plan, exe.metas, BATCH))
    assert _carry_arrays(exe.plan, exe.metas) == len(leaves)


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


def test_temporal_launch_with_a_back_edge_builds_its_carry():
    exe, spikes = _net(LOOP)
    rec = _record(exe, spikes, temporal=True)
    carry = [a for n, a, _ in rec.opened if n == "launch.carry"]
    tp = exe._temporal_structure()
    assert tp.block
    assert carry == [{"arrays": _carry_arrays(tp.sub_plan, exe.metas)}]


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
