"""The Potjans-Diesmann microcircuit configuration, cut to a CPU size as
NEST's ``N_scaling`` and ``K_scaling`` cut it: through the normal path
(classifier switching, the fused scan) bit for bit against the
configuration's own reference; its counters against the trains; its
readers on stub contexts.  Also the spike-driven form on the recurrent
fixtures, and gesture's forms as they were."""
import json
import time
import types

import numpy as np
import pytest

import faultkit
from chipbench import harness, simulate, spec, trace, work
from chipbench.spans import Span

CELL = "microcircuit.thalamic_t256"
CONFIG = spec.load_json(spec.BENCH_DIR / "configs" / "microcircuit.json")
#: N at 0.005 of the published sizes; K at 0.5 keeps NEST's DC drive
#: positive, so the populations fire after the step-0 volley
SMALL = {"network": dict(CONFIG["network"], N_scaling=0.005, K_scaling=0.5)}
SHORT = {"steps": 48}
SEED = 2**31 + 16


def _generator():
    return spec.load_module("configs/microcircuit/net.py")


def _small_spec():
    return _generator().generate(spec.merged(CONFIG, SMALL))


def test_full_scale_widths_are_the_published_ones():
    """77,169 neurons, 2.99e8 recurrent synapses in 55 projections (and
    3.1e6 in 4 thalamic ones), 44 recurrent projections over the dense
    cap (counted, not drawn)."""
    gen = _generator()
    net = CONFIG["network"]
    sizes = gen.sizes(net)
    assert sum(v for k, v in sizes.items() if k != "TH") == 77_169
    assert sizes["TH"] == 902
    k, k_th = gen.synapse_counts(net)
    assert (k > 0).sum() == 55 and (k_th > 0).sum() == 4
    assert 2.98e8 < k.sum() < 3.0e8 and 3.0e6 < k_th.sum() < 3.2e6
    names = net["populations"]
    over = sum(1 for t in range(8) for s in range(8) if k[t, s] > 0
               and sizes[names[s]] * sizes[names[t]] > 2**24)
    assert over == 44


def test_generator_and_program_agree_on_constants_and_back_edges():
    sp = _small_spec()
    gen = _generator()
    net = gen.to_program(sp)
    assert len(sp.back_edges) > 0
    assert net.back_edges == frozenset(sp.back_edges)
    for pop, p in zip(net.populations[1:], sp.pops[1:]):
        fixed = pop.lif.fixed()
        assert {k: getattr(fixed, k) for k in gen.FIXED_KEYS} == {
            k: p.params[k] for k in gen.FIXED_KEYS}
        np.testing.assert_array_equal(pop.lif.v0_fixed(pop.size),
                                      p.params["v0"])
    for e in sp.projs:
        assert e.indices.dtype == np.int32 and e.values.dtype == np.int8
        assert e.delays.dtype == np.int16
    back = [sp.projs[i] for i in sp.back_edges]
    assert min(int(e.delays.min()) for e in back) == 0
    assert min(int(e.delays.min()) for i, e in enumerate(sp.projs)
               if i not in sp.back_edges) >= 1
    # multapses: some (pre, post) pair repeats
    e = max(sp.projs, key=lambda e: e.n_synapses)
    pairs = e.sources().astype(np.int64) * e.n_target + e.indices
    assert np.unique(pairs).size < pairs.size


@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_reference_loop_equals_simulate_graph(dtype):
    """The reference's own event loop (integer rings, one ``np.add.at`` a
    target a step) gives ``graph.simulate_graph``'s trains with the same
    update, in the stated fixed point and in the control's bfloat16."""
    import ml_dtypes
    from chipbench import graph

    ref = spec.load_module("configs/microcircuit/reference.py")
    dtype = ml_dtypes.bfloat16 if dtype == "bfloat16" else dtype
    update = ref.iaf_fixed if dtype == np.float32 else ref.iaf_float
    sp = _small_spec()
    x = simulate.poisson_inputs(sp, {"steps": 40, "batch": 2}, 9, count=1)[0]
    got = ref.simulate(sp, x, dtype=dtype)
    want = graph.simulate_graph(sp, x, dtype=dtype, update=update)
    assert sum(int(z.sum()) for z in want.values()) > 0
    for name, z in want.items():
        np.testing.assert_array_equal(got[name], z)


def _setup_note(capsys):
    notes = [json.loads(line) for line in capsys.readouterr().err.splitlines()
             if line.startswith("{")]
    return next(n for n in notes if n.get("stage") == "setup")


def _spike_everywhere(monkeypatch, slots=None):
    """Price the spike-driven form's fixed cost at 0, so every projection
    whose synapses pack takes it (at this size the cost model would keep
    the O(B R) forms); optionally shrink its passes to ``slots`` synapse
    slots (full) and a quarter of that (light)."""
    from repro.core.cost_model import SerialBatchCostModel
    from repro.core.runtime import executor, serial_runtime

    monkeypatch.setattr(executor, "DEFAULT_SERIAL_BATCH_COST",
                        SerialBatchCostModel(spike_base=0.0))
    if slots is not None:
        monkeypatch.setattr(serial_runtime, "FULL_SLOTS", slots)
        monkeypatch.setattr(serial_runtime, "LIGHT_SLOTS", slots // 4)


def test_scaled_microcircuit_is_correct_through_the_normal_path(
        monkeypatch, capsys):
    r = faultkit.run(CELL, faultkit.SOUND, monkeypatch, config=SMALL,
                     traffic=SHORT, seed=SEED)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1
    # at this size every pair repeats many times: the summed weights leave
    # int8, so the classifier policy compiles every projection serial
    assert set(_setup_note(capsys)["paradigms"]) == {"serial"}


def test_scaled_microcircuit_spike_driven_with_extra_passes(
        monkeypatch, capsys):
    _spike_everywhere(monkeypatch, slots=1)
    r = faultkit.run(CELL, faultkit.SOUND, monkeypatch, config=SMALL,
                     traffic=SHORT, seed=SEED + 1)
    assert r["correct"] is True, r["checks"]
    forms = _setup_note(capsys)["forms"]["fused|1"]
    assert forms.count("spike") >= 20


def _record_launches(monkeypatch, log):
    """Keep each read launch's input, trains and counter totals."""
    from repro.core.runtime.executor import NetworkExecutable

    orig = NetworkExecutable.run

    def run(self, spikes, **kw):
        outs = orig(self, spikes, **kw)
        if self.launch_counts:
            log.append((np.asarray(spikes), outs, self.launch_counts[-1],
                        self.report.serial_forms[("fused", spikes.shape[1])]))
        return outs

    monkeypatch.setattr(NetworkExecutable, "run", run)


def test_traced_run_counters_are_consistent_with_the_trains(
        monkeypatch, tmp_path):
    from repro import tracing

    _spike_everywhere(monkeypatch, slots=64)
    log = []
    _record_launches(monkeypatch, log)
    before = tracing.counts()
    r = harness.run(CELL, SEED + 2, 1.0, True, t_process=time.perf_counter(),
                    require_tpu=False, config_overrides=SMALL,
                    traffic_overrides={"steps": 24}, trace_dir=tmp_path)
    assert r["correct"] is True, r["checks"]
    assert r["metrics"]["syn_events_per_s.sim"]["value"] > 0
    after = tracing.counts()
    assert log
    sp = _small_spec()
    for name in ("events.routed", "spikes.emitted", "route.passes"):
        assert after.get(name, 0) - before.get(name, 0) == sum(
            c[name] for _, _, c, _ in log)
    assert sum(c["route.passes"] for _, _, c, _ in log) > 0
    for x, outs, counts, forms in log:
        trains = {e.post: z for e, z in zip(sp.projs, outs)}
        trains["TH"] = x
        assert counts["spikes.emitted"] == sum(
            int(z.sum()) for name, z in trains.items() if name != "TH")
        events = 0
        for i, (e, form) in enumerate(zip(sp.projs, forms)):
            if form != "spike":
                continue
            z = trains[e.pre][:-1] if i in sp.back_edges else trains[e.pre]
            events += int((z * np.diff(e.indptr)[None, None, :]).sum())
        assert counts["events.routed"] == events


# -- readers on stub contexts --------------------------------------------------

def _stub(launch_counts=None, kernel_ops=()):
    host = [("window", 0.0, 10.0), ("sim.launch", 0.0, 4.0),
            ("sim.launch", 4.0, 8.0), ("sim.launch", 8.0, 9.9)]
    ran = [Span("sim.launch", a, b, {"batch": 1, "steps": 256})
           for _, a, b in host[1:]]
    r = trace.Reduced(
        window=(0.0, 10.0),
        ops={"/device:TPU:0": [trace.Op(n, a, b) for n, a, b in kernel_ops]},
        host=[trace.Op(n, a, b) for n, a, b in host])
    pops = [types.SimpleNamespace(is_input=True, size=9, params={}),
            types.SimpleNamespace(is_input=False, size=1000,
                                  params={"p22": 1}),
            types.SimpleNamespace(is_input=False, size=24,
                                  params={"p22": 1})]
    return types.SimpleNamespace(
        trace=r, launches=ran[:2], ran=ran, window=(0.0, 10.0),
        peaks=work.peaks_for("TPU v5 lite"),
        spec=types.SimpleNamespace(pops=pops),
        exe=types.SimpleNamespace(launch_counts=launch_counts))


def test_syn_events_reads_the_window_launches_counters():
    read = spec.load_reader("syn_events_per_s.sim")
    warm = [{"events.routed": 10**9}] * 2
    window = [{"events.routed": 3000}, {"events.routed": 5000},
              {"events.routed": 7}]
    assert read(_stub(warm + window)) == pytest.approx(8000 / 8.0)
    assert read(_stub(window[:2])) is None          # fewer than ran
    assert read(_stub(None)) is None                # a program without them
    assert read(_stub([])) is None


def test_iaf_roofline_counts_unpadded_neurons_per_call():
    read = spec.load_reader("iaf_psc_exp_roofline")
    metric = spec.load_module("metrics/iaf_psc_exp_roofline.py")
    # 2 populations x 256 steps x 3 launches = 1536 calls of 1 ns each
    calls = [(f"iaf_psc_exp_pallas.{k % 2}", k * 1e-3, k * 1e-3 + 1e-9)
             for k in range(1536)]
    ctx = _stub(kernel_ops=calls)
    w = (metric.iaf_psc_exp(1000, 1) + metric.iaf_psc_exp(24, 1)) * 256 * 3
    least, bound = work.least_time(w, ctx.peaks)
    assert bound == "bandwidth"
    assert read(ctx) == pytest.approx(100.0 * least / (1536 * 1e-9))
    assert read(_stub(kernel_ops=calls[:-1])) is None
    ctx.spec.pops = ctx.spec.pops[:1]
    assert read(ctx) is None


# -- the spike-driven form on the recurrent fixtures ---------------------------

BENCH = faultkit.fixture_bench("recurrent", "recurrent_wide")


@pytest.mark.parametrize("cell, slots", [("recurrent.scan_t256", 1),
                                         ("recurrent_wide.scan_t256", 1024)])
def test_recurrent_fixtures_spike_driven_with_extra_passes(
        cell, slots, monkeypatch, capsys):
    """Passes of one spike a lane (the small fixture) or of about 50 (the
    wide one, whose 8192 inputs fire about 1,600 a lane a step)."""
    _spike_everywhere(monkeypatch, slots=slots)
    r = faultkit.run(cell, faultkit.SOUND, monkeypatch, bench=BENCH,
                     traffic={"steps": 24}, seconds=4.0)
    assert r["correct"] is True, r["checks"]
    assert "spike" in _setup_note(capsys)["forms"]["fused|8"]


def test_recurrent_fixture_forms_agree_bit_for_bit(monkeypatch):
    from repro.core.runtime import network_executable, serial_runtime

    monkeypatch.setattr(serial_runtime, "FULL_SLOTS", 1)
    cell = spec.load_cell("recurrent.scan_t256", BENCH)
    gen = spec.network_code(cell.config, "generator")
    sp = gen.generate(cell.config)
    net = gen.to_program(sp)
    from chipbench.spans import Spans
    report = harness._compile(cell.config, net, Spans())
    exe = network_executable(net, report)
    x = simulate.poisson_inputs(sp, {"steps": 40, "batch": 3}, 5, count=1)[0]
    runs = {f: exe.run(x.astype(np.float32), serial_form=f)
            for f in ("event", "sparse", "dense", "spike")}
    assert exe.launch_counts[-1]["route.passes"] > 0
    for f, outs in runs.items():
        for a, b in zip(outs, runs["event"]):
            np.testing.assert_array_equal(a, b)


def test_gesture_forms_are_unchanged():
    """The serial layer keeps the sparse form at batch 8 (scan cell) and
    the temporal forms stay (window cell); the spike-driven form's fixed
    cost keeps it off a 20 -> 4 layer."""
    from chipbench.spans import Spans
    from repro.core.runtime import network_executable

    cell = spec.load_cell("gesture.scan_t256")
    gen = spec.network_code(cell.config, "generator")
    net = gen.to_program(gen.generate(cell.config))
    report = harness._compile(cell.config, net, Spans())
    exe = network_executable(net, report)
    assert [l.paradigm for l in report.layers] == ["parallel", "serial"]
    assert exe.serial_forms(8) == ("-", "sparse")
    assert exe.serial_forms(1) == ("-", "event")
    assert exe.temporal_forms(8, 256) == ("temporal", "temporal_sparse")
