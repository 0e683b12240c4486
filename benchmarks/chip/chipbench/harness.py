"""One run of one cell: set-up, the measured window, the check, the metrics.

The harness holds no branch per cell.  The configuration file says how to
build and compile the network, and names the modules that generate it and
simulate it plainly (``spec.network_code``); the traffic file gives the
one general driver (``simulate.SimDriver``) its shapes and path; each
metric is read by its own reader from the ``Context`` this module fills.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import json
import sys
import tempfile
import time
import types
from pathlib import Path
from typing import Callable, Dict, List, Optional

from . import spec as specmod, trace as tracemod, work
from .simulate import SimDriver
from .spans import Spans


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Context:
    """What a metric reader may read: a reader added later finds the cell,
    its traffic parameters and every span and count here, and needs no
    change to this module."""

    cell: str
    setup_s: float
    seconds: float                 # the measured window's length
    window: tuple                  # (start, end) on the host clock
    spans: Spans
    launches: List                 # launch spans that ended in the window
    ran: List                      # every launch span of the traced period
    trace: Optional["tracemod.Reduced"]
    spec: object                   # the configuration's NetSpec
    exe: object                    # the NetworkExecutable (shapes only)
    peaks: dict
    traffic: dict


def _compile(cfg: dict, net, spans: Spans):
    """The switching compile of ``net`` by the prejudging classifier,
    trained on the configuration's stored data set.

    A CSR projection over the program's dense element cap compiles serial,
    by the program's own rule for such projections
    (``repro.scaffold.cerebellum.scaffold_policies``): the parallel
    paradigm stores its weights dense.
    """
    from repro.core import SwitchingCompiler, train_switch_classifier
    from repro.core.dataset import ParadigmDataset
    from repro.core.layer import DENSE_ELEMENT_CAP, is_sparse
    from repro.core.switching import CompileReport

    how = cfg["compile"]
    with spans.span("setup.classifier"):
        data = ParadigmDataset.load(str(specmod.config_path(how["dataset"])))
        clf, _ = train_switch_classifier(data, seed=int(how["classifier_seed"]))
    with spans.span("setup.switching"):
        prejudged = SwitchingCompiler("classifier", clf)
        serial = SwitchingCompiler("serial")
        return CompileReport([
            (serial if is_sparse(e)
             and e.n_source * e.n_target > DENSE_ELEMENT_CAP
             else prejudged).compile_layer(e) for e in net.layers])


def _device(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX has "
                     f"{len(devices)} {devices[0].platform} device(s)")
    return devices


class _CompileCounter:
    """Counts JAX traces, compiles and cache loads while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.on = False
        self.counts: Dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event in self.EVENTS:
            self.counts[event.rsplit("/", 1)[-1]] = (
                self.counts.get(event.rsplit("/", 1)[-1], 0) + 1)


class _GcPauses:
    """Number and seconds of the interpreter's garbage collections until
    ``close``: a pause the host takes inside the window shows in the tails."""

    def __init__(self):
        self.count, self.seconds, self._t0 = 0, 0.0, None
        gc.callbacks.append(self._event)

    def _event(self, phase, _info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.count += 1
            self.seconds += time.perf_counter() - self._t0

    def close(self):
        gc.callbacks.remove(self._event)


def _slow(launches, t0: float, factor: float = 10.0, most: int = 10):
    """[seconds into the window, ms] of launches that took over ``factor``
    times the median launch: the host's stalls, which the rate counts."""
    if not launches:
        return []
    ms = sorted(s.seconds for s in launches)
    median = ms[len(ms) // 2]
    return [[round(s.t0 - t0, 3), round(1e3 * s.seconds, 1)] for s in launches
            if s.seconds > factor * median][:most]


def note(**fields) -> None:
    """An informational line on standard error (never the result line)."""
    print(json.dumps(fields, default=float), file=sys.stderr, flush=True)


def _compile_cache() -> str:
    """JAX's persistent cache where the program keeps it (``run.py`` sets
    ``JAX_COMPILATION_CACHE_DIR`` to a fixed directory in the checkout),
    holding every program however fast it compiled."""
    import jax
    from repro.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, require_tpu: bool = True,
        config_overrides: Optional[dict] = None,
        traffic_overrides: Optional[dict] = None,
        trace_dir: Optional[Path] = None,
        tamper: Optional[Callable] = None,
        bench: Optional[dict] = None) -> dict:
    """Run cell ``workload`` of ``bench`` (default: ``BENCHMARK.json``)
    once; returns the result line's object.

    ``tamper(driver)``, where given, runs after the window and before the
    check: the control and the fault tests put their answers in the
    program's place through it.
    """
    cell = specmod.load_cell(workload, bench)
    config = specmod.merged(cell.config, config_overrides)
    traffic = specmod.merged(cell.traffic, traffic_overrides)
    devices = _device(cell.chips, require_tpu)
    dev = devices[0]
    peaks = work.peaks_for(dev.device_kind) if require_tpu else None
    # tests run without the chip and leave the persistent cache alone
    cache_dir = _compile_cache() if require_tpu else None
    counter = _CompileCounter()
    spans = Spans()

    import jax

    spans.annotate_with(jax.profiler.TraceAnnotation)
    h = types.SimpleNamespace(traffic=traffic, seed=seed, spans=spans)
    with spans.span("setup.network"):
        generator = specmod.network_code(config, "generator")
        h.spec = generator.generate(config)
        h.net = generator.to_program(h.spec)
    h.report = _compile(config, h.net, spans)
    driver = SimDriver(h)
    with spans.span("setup.warmup"):
        driver.warm()
    note(stage="setup", neurons=h.spec.n_neurons, synapses=h.spec.n_synapses,
         paradigms=[l.paradigm for l in h.report.layers],
         forms={f"{p}|{b}": list(f) for (p, b), f in
                h.report.serial_forms.items()})

    if trace:
        seconds = min(seconds, float(traffic["trace_seconds"]))
        keep_trace = trace_dir is not None
        trace_dir = trace_dir or Path(tempfile.mkdtemp(prefix="chip_trace_"))
        jax.profiler.start_trace(str(trace_dir))
    setup_s = time.perf_counter() - t_process
    counter.on = True
    pauses = _GcPauses()
    try:
        with spans.span("window"):
            driver.run_window(seconds, seed)
    finally:
        counter.on = False
        pauses.close()
        if trace:
            jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    launched = driver.window_launches()
    note(stage="window", cache_dir=cache_dir,
         compiles_in_window=counter.counts,
         gc_pauses=pauses.count, gc_s=pauses.seconds,
         longest_launch_ms=1e3 * max((s.seconds for s in launched), default=0),
         slow_launches=_slow(launched, driver.window[0]),
         setup_parts={s.name: round(s.seconds, 6) for s in spans.items
                      if s.name.startswith("setup.")})

    exe = driver.exe
    attempted, failed = driver.attempted_failed()
    driver.release()
    gc.collect()
    # the reference is the check's, so not set-up: loaded once the window
    # has closed
    h.reference = specmod.network_code(config, "reference")
    if tamper is not None:
        tamper(driver)

    t_check = time.perf_counter()
    checks = driver.check()
    note(stage="check", seconds=time.perf_counter() - t_check)

    reduced = None
    if trace:
        reduced = tracemod.reduce(trace_dir, "window",
                                  known_spans={s.name for s in spans.items})
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(cell=workload, setup_s=setup_s, seconds=seconds,
                  window=driver.window, spans=spans, launches=launched,
                  ran=driver.ran,
                  trace=reduced, spec=h.spec, exe=exe, peaks=peaks,
                  traffic=traffic)
    metrics = {}
    for m in (cell.metrics_layer if trace else cell.metrics_e2e):
        value = m.read(ctx)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": all(lim is None or v <= lim
                             for v, lim in checks.values()),
              "attempted": attempted, "failed": failed, "metrics": metrics,
              "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result
