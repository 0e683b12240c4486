"""The spike-driven serial form, delay 0 on back-edges and multapses.

* the spike-driven form gives the event, sparse and dense forms' bits,
  with its pass capacities forced small so that steps take extra passes;
* a back-edge storing ``d - 1`` delivers a spike at exactly ``t + d``;
* multapses (repeated (pre, post) entries, differing delays) are summed
  by every form, by ``densify`` where their delays agree, and by the
  parallel compile per delay;
* the choosing policies (classifier, ideal) send what the parallel
  paradigm cannot hold to serial without a choice.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SwitchingCompiler
from repro.core.layer import (
    LIFParams,
    Population,
    SNNNetwork,
    SparseProjection,
)
from repro.core.runtime import executor, network_executable, serial_runtime
from repro.core.runtime.executor import NetworkExecutable
from repro.core.runtime.reference import run_graph_reference
from repro.core.switching import CompileReport, parallel_can_hold

LIF = LIFParams(alpha=0.5, v_th=64.0)


def _csr(pre, post, s, t, n, d_lo, d_hi, sign, rng, w_hi=40):
    src = np.sort(rng.integers(0, s, n))
    indptr = np.zeros(s + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=s), out=indptr[1:])
    return SparseProjection(
        n_source=s, n_target=t, indptr=indptr,
        indices=rng.integers(0, t, n),
        values=sign * rng.integers(1, w_hi, n).astype(np.float64),
        delay_values=rng.integers(d_lo, d_hi + 1, n), delay_range=d_hi,
        lif=LIF, name=f"{pre}->{post}", pre=pre, post=post)


def _recurrent(seed=1):
    """x -> E, E -> E (back-edge, delays from 0), E -> I, I -> E (back-edge,
    delays from 0): multapses everywhere (n draws over few pairs)."""
    rng = np.random.default_rng(seed)
    pops = [Population("x", 50), Population("E", 40, LIF),
            Population("I", 20, LIF)]
    projs = [_csr("x", "E", 50, 40, 900, 1, 7, 1, rng),
             _csr("E", "E", 40, 40, 500, 0, 9, 1, rng),
             _csr("E", "I", 40, 20, 300, 1, 5, 1, rng),
             _csr("I", "E", 20, 40, 300, 0, 6, -1, rng)]
    return SNNNetwork(populations=pops, projections=projs)


@pytest.mark.parametrize("batch", [1, 3])
def test_spike_form_matches_every_form_with_small_capacities(
        monkeypatch, batch):
    net = _recurrent()
    assert net.back_edges == frozenset({1, 3})
    report = SwitchingCompiler("serial").compile_network(net)
    x = (np.random.default_rng(2).random((40, batch, 50)) < 0.3
         ).astype(np.float32)
    exe = network_executable(net, report)
    want = run_graph_reference(net, x)
    for form in ("event", "sparse", "dense", "spike"):
        got = exe.run(x, serial_form=form)
        assert report.serial_forms[("fused", batch)] == (form,) * 4
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
    roomy = exe.launch_counts[-1]
    monkeypatch.setattr(serial_runtime, "FULL_SLOTS", 1)
    small = NetworkExecutable.build(net, report)
    assert all(m.spike.full == m.spike.light == 1 for m in small.metas)
    for batched in (False, True):
        got = small.run(x, serial_form="spike", batched=batched)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        tight = small.launch_counts[-1]
        assert tight["route.passes"] > roomy["route.passes"]
        assert tight["events.routed"] == roomy["events.routed"]
        assert tight["spikes.emitted"] == roomy["spikes.emitted"]
    # light and full passes of different sizes in one step
    monkeypatch.setattr(serial_runtime, "FULL_SLOTS", 64)
    monkeypatch.setattr(serial_runtime, "LIGHT_SLOTS", 16)
    mixed = NetworkExecutable.build(net, report)
    assert all(m.spike.light < m.spike.full for m in mixed.metas)
    got = mixed.run(x, serial_form="spike")
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert mixed.launch_counts[-1]["events.routed"] == roomy["events.routed"]


def _fired_rows(e, z_src):
    """Synapses routed from a (T, B, S) source train through CSR ``e``."""
    lengths = np.diff(e.indptr)
    return int((z_src * lengths[None, None, :]).sum())


def test_counters_are_consistent_with_the_trains():
    net = _recurrent(4)
    report = SwitchingCompiler("serial").compile_network(net)
    exe = network_executable(net, report)
    x = (np.random.default_rng(5).random((30, 2, 50)) < 0.3
         ).astype(np.float32)
    outs = exe.run(x, serial_form="spike")
    counts = exe.launch_counts[-1]
    trains = {"x": x, "E": outs[0], "I": outs[2]}
    events = 0
    for i, e in enumerate(net.layers):
        z = trains[e.pre]
        if i in net.back_edges:           # routed a step later, never the last
            z = z[:-1]
        events += _fired_rows(e, z)
    assert counts["events.routed"] == events
    assert counts["spikes.emitted"] == int(outs[0].sum() + outs[2].sum())
    assert counts["route.passes"] >= 0


def test_counters_only_where_a_projection_is_spike_driven():
    net = _recurrent()
    report = SwitchingCompiler("serial").compile_network(net)
    exe = network_executable(net, report)
    x = np.zeros((4, 1, 50), np.float32)
    exe.run(x, serial_form="event")
    assert exe.launch_counts == []
    exe.run(x, serial_form="spike")
    assert len(exe.launch_counts) == 1


@pytest.mark.parametrize("d", [1, 2, 5])
def test_back_edge_of_published_delay_d_arrives_at_t_plus_d(d):
    """x -> B forward, B -> A a back-edge storing d - 1: one spike of B at
    step t reaches A at exactly t + d, in every form."""
    big = LIFParams(alpha=0.0, v_th=64.0)
    pops = [Population("x", 1), Population("A", 1, big),
            Population("B", 1, big)]

    def one(pre, post, delay):
        return SparseProjection(
            n_source=1, n_target=1, indptr=np.array([0, 1]),
            indices=np.array([0]), values=np.array([100.0]),
            delay_values=np.array([delay]), delay_range=max(1, delay),
            lif=big, name=f"{pre}->{post}", pre=pre, post=post)

    net2 = SNNNetwork(populations=pops, projections=[
        one("x", "B", 1), one("B", "A", d - 1)], forced_back_edges=[1])
    assert net2.back_edges == frozenset({1})
    report = SwitchingCompiler("serial").compile_network(net2)
    exe = network_executable(net2, report)
    x = np.zeros((12, 1, 1), np.float32)
    x[2, 0, 0] = 1.0                       # B fires at step 3
    for form in ("event", "sparse", "dense", "spike"):
        out_b, out_a = exe.run(x, serial_form=form)
        t_b = int(np.flatnonzero(out_b[:, 0, 0])[0])
        t_a = int(np.flatnonzero(out_a[:, 0, 0])[0])
        assert t_b == 3 and t_a == t_b + d, (form, t_b, t_a)
    want = run_graph_reference(net2, x)
    np.testing.assert_array_equal(want[1], out_a)


def test_delay_0_only_on_a_back_edge():
    rng = np.random.default_rng(0)
    pops = [Population("x", 5), Population("E", 4, LIF)]
    fwd = _csr("x", "E", 5, 4, 10, 0, 3, 1, rng)
    with pytest.raises(ValueError, match="delay 0 is only allowed"):
        SNNNetwork(populations=pops, projections=[fwd])
    with pytest.raises(ValueError, match="delay outside"):
        SparseProjection(n_source=1, n_target=1, indptr=np.array([0, 1]),
                         indices=np.array([0]), values=np.array([1.0]),
                         delay_values=np.array([-1]), delay_range=2,
                         name="bad", pre="x", post="E")


def _multapses():
    """Two sources, two targets: pair (0, 1) three times with delays 2, 2
    and 5; pair (1, 0) twice at delay 1."""
    return SparseProjection(
        n_source=2, n_target=2, indptr=np.array([0, 3, 5]),
        indices=np.array([1, 1, 1, 0, 0]),
        values=np.array([10.0, 20.0, -7.0, 30.0, 40.0]),
        delay_values=np.array([2, 2, 5, 1, 1]), delay_range=5,
        lif=LIF, name="m", pre="x", post="E")


def test_densify_sums_multapses_of_one_delay_and_refuses_differing():
    with pytest.raises(ValueError, match="differ in delay"):
        _multapses().densify()
    m = _multapses()
    keep = np.array([True, True, False, True, True])
    same = SparseProjection(
        n_source=2, n_target=2, indptr=np.array([0, 2, 4]),
        indices=m.indices[keep], values=m.values[keep],
        delay_values=m.delay_values[keep], delay_range=5, lif=LIF,
        name="m", pre="x", post="E")
    d = same.densify()
    assert d.weights.tolist() == [[0.0, 30.0], [70.0, 0.0]]
    assert d.delays[0, 1] == 2 and d.delays[1, 0] == 1


@pytest.mark.parametrize("policy", ["serial", "parallel"])
def test_multapses_with_differing_delays_in_every_form(policy):
    pops = [Population("x", 2), Population("E", 2, LIF)]
    net = SNNNetwork(populations=pops, projections=[_multapses()])
    report = CompileReport([SwitchingCompiler(policy).compile_layer(
        net.layers[0])])
    prog = report.layers[0].program
    if policy == "parallel":
        slices = {s.delay: s.matrix[:2, :len(s.col_sources)]
                  for s in prog.slices}
        assert slices[2][1, 0] == 30 and slices[5][1, 0] == -7
        assert slices[1][0, 0] == 70
    exe = network_executable(net, report)
    x = np.zeros((12, 1, 2), np.float32)
    x[0, 0, :] = 1.0
    want = run_graph_reference(net, x)
    forms = ("event", "sparse", "dense", "spike") if policy == "serial" \
        else ("auto",)
    for form in forms:
        (got,) = exe.run(x, serial_form=form)
        np.testing.assert_array_equal(got, want[0])
    # target 1 gets 30 at step 2 (no spike: 30 < 64) then -7 at step 5;
    # target 0 gets 70 at step 1 and fires
    assert got[1, 0, 0] == 1.0 and got[:, 0, 1].sum() == 0


def test_classifier_policy_sends_what_parallel_cannot_hold_to_serial():
    class AlwaysParallel:
        def predict(self, x):
            return np.ones(len(x), np.int64)

    rng = np.random.default_rng(3)
    fits = _csr("x", "E", 30, 20, 100, 1, 4, 1, rng, w_hi=20)
    # one pair, one delay, twice: 100 + 100 leaves int8
    over = SparseProjection(
        n_source=1, n_target=1, indptr=np.array([0, 2]),
        indices=np.array([0, 0]), values=np.array([100.0, 100.0]),
        delay_values=np.array([3, 3]), delay_range=3, lif=LIF, name="o",
        pre="x", post="E")
    wide = _csr("x", "E", 5000, 4000, 100, 1, 4, 1, rng)
    clf = SwitchingCompiler("classifier", AlwaysParallel())
    assert parallel_can_hold(fits) and clf.compile_layer(fits).paradigm == \
        "parallel"
    ideal = SwitchingCompiler("ideal")
    for layer in (over, wide):
        assert not parallel_can_hold(layer)
        assert clf.compile_layer(layer).paradigm == "serial"
        compiled = ideal.compile_layer(layer)
        assert compiled.paradigm == "serial" and compiled.n_compilations == 1
    with pytest.raises(ValueError, match="int8"):
        SwitchingCompiler("parallel").compile_layer(over)


# -- counter digits ----------------------------------------------------------

def _digits(totals):
    """int64 totals (..., n) as the launch's (..., n, 2) int32 digits."""
    t = np.asarray(totals, np.int64)
    return np.stack([t & (2**24 - 1), t >> 24], axis=-1).astype(np.int32)


@pytest.mark.parametrize("start,steps", [
    ([2**24 - 1, 0, 5], [[1, 0, 0]]),                  # carry into hi
    ([2**24 - 3, 2**24 - 1, 0], [[2, 1, 0], [2, 1, 7]]),
    ([0, 0, 0], [[2**23, 2**22, 1]] * 300),            # past 2**31
    ([2**31 - 1, 2**32 + 5, 0], [[2**24 - 1, 1, 2**24]]),
])
def test_count_add_carries_exactly(start, steps):
    acc = jnp.asarray(_digits(start))
    for s in steps:
        acc = executor._count_add(acc, jnp.asarray(s, jnp.int32))
    a = np.asarray(acc)
    assert (a[:, 0] >= 0).all() and (a[:, 0] < 2**24).all()
    want = np.asarray(start, np.int64) + np.asarray(steps, np.int64).sum(0)
    got = executor.read_counters(a)
    assert list(got) == list(executor.LAUNCH_COUNTERS)
    assert list(got.values()) == want.tolist()


@pytest.mark.parametrize("lead", [(5,), (2, 3)])
def test_read_counters_sums_leading_lane_axes(lead):
    rng = np.random.default_rng(len(lead))
    totals = rng.integers(0, 2**33, lead + (3,))
    got = executor.read_counters(jnp.asarray(_digits(totals)))
    assert list(got.values()) == totals.reshape(-1, 3).sum(0).tolist()
    assert max(got.values()) > 2**31
