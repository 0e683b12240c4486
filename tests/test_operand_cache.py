"""NetworkExecutable's operand cache: one dict keyed by (operand kind,
projection), filled on a form's first launch and emptied by ``shard()``.

Forms that read the same arrays share one entry, so no operand is held
twice on the device: a serial ``temporal`` projection reads the dense
form's weights and ``temporal_sparse`` the sparse form's ELL operands.
"""
import contextlib

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import tracing
from repro.core import SwitchingCompiler
from repro.core.layer import LIFParams, Population, SNNNetwork, random_projection
from repro.core.runtime import executor, network_executable
from repro.core.switching import CompileReport
from repro.placement import DeviceAssignment

LIF = LIFParams(alpha=0.5, v_th=64.0)


def _chain(paradigms=("serial", "serial")):
    """in (24) -> h (16) -> out (8), each projection compiled under the
    given paradigm, and a (12, 2) input train."""
    pops = [Population(f"oc.{n}", s) for n, s in (("in", 24), ("h", 16),
                                                  ("out", 8))]
    projs = []
    for i in range(2):
        p = random_projection(pops[i], pops[i + 1], 0.4, 3, seed=90 + i)
        p.lif = LIF
        projs.append(p)
    net = SNNNetwork(populations=pops, projections=projs)
    report = CompileReport(layers=[
        SwitchingCompiler(par).compile_layer(l)
        for par, l in zip(paradigms, net.layers)
    ])
    x = (np.random.default_rng(5).random((12, 2, 24)) < 0.4
         ).astype(np.float32)
    return network_executable(net, report), x


def _same(a, b):
    for u, v in zip(a, b):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


@pytest.mark.parametrize("form,temporal_form,kind", [
    ("dense", "temporal", "dense"),
    ("sparse", "temporal_sparse", "sparse"),
])
def test_temporal_forms_reuse_the_step_forms_operands(form, temporal_form,
                                                      kind):
    exe, x = _chain()
    want = exe.run(x, serial_form=form)
    held = dict(exe._operands)
    assert set(held) == {(kind, 0), (kind, 1)}
    got = exe.run(x, temporal=True, serial_form=form)
    assert exe.temporal_forms(2, 12, form) == (temporal_form,) * 2
    assert exe._operands == held          # nothing new was built
    for i in range(2):
        for a, b in zip(exe._operands_for(i, temporal_form),
                        exe._operands_for(i, form)):
            assert a is b
    _same(got, want)


def test_parallel_temporal_operand_is_its_own_kind():
    """A parallel projection's whole-train operand is scattered from its
    WDM stack: its own kind, never confused with a serial dense one."""
    exe, x = _chain(("parallel", "serial"))
    want = exe.run(x, serial_form="dense")
    got = exe.run(x, temporal=True, serial_form="dense")
    assert set(exe._operands) == {("dense", 1), ("temporal", 0)}
    (w,) = exe._operands[("temporal", 0)]
    assert w.shape == (4, 24, 16)
    _same(got, want)


@pytest.mark.parametrize("form", ["event", "sparse", "dense", "spike"])
def test_a_second_launch_builds_nothing(monkeypatch, form):
    built = []

    def counting(kind, build):
        def wrapped(exe, i):
            built.append((kind, i))
            return build(exe, i)
        return wrapped

    for kind, build in list(executor._OPERAND_MAKERS.items()):
        monkeypatch.setitem(executor._OPERAND_MAKERS, kind,
                            counting(kind, build))
    spans = []

    @contextlib.contextmanager
    def sink(name, **attrs):
        spans.append(name)
        yield

    exe, x = _chain()
    with tracing.installed(sink):
        first = exe.run(x, serial_form=form)
        assert built == [(form, 0), (form, 1)]
        n_spike = spans.count("lower.spike")
        assert n_spike == (2 if form == "spike" else 0)
        second = exe.run(x, serial_form=form)
    assert built == [(form, 0), (form, 1)]
    assert spans.count("lower.spike") == n_spike
    _same(second, first)


@pytest.mark.parametrize("branch", ["mesh", "assignment"])
def test_shard_empties_the_operand_cache_and_jit_entries(branch):
    exe, x = _chain()
    want = exe.run(x, serial_form="sparse")
    assert exe._operands and exe.jit_entries() == 1
    if branch == "mesh":
        exe.shard(Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                       ("data", "model")))
    else:
        exe.shard(assignment=DeviceAssignment(
            n_devices=1, groups=((0,),), tile_device={},
            proj_device=(0, 0), halo=()))
    assert exe._operands == {} and exe.jit_entries() == 0
    _same(exe.run(x, serial_form="sparse"), want)
    assert set(exe._operands) == {("sparse", 0), ("sparse", 1)}
