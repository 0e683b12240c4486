"""Back-to-back simulation launches: the driver and the check.

Set-up draws ``DISTINCT_INPUTS`` Bernoulli trains of the traffic's
(steps, batch) shape from the seed, at the input populations' rates, and
puts them on the device; the window launches
``NetworkExecutable.run`` on them in turn, back to back, each launch's
outputs reaching the host before the next starts.  A seed-drawn uniform
sample of ``KEPT`` of the window's launches (reservoir sampling, decided
as each launch completes) keeps its outputs; once the window has closed,
each kept launch is compared with the reference's run of its input.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List

import numpy as np

from . import reference

#: Input trains drawn per run, launched in turn.
DISTINCT_INPUTS = 16
#: Launches of the window whose outputs the check compares.
KEPT = 64


def seed_sequence(seed: int, stream: int) -> np.random.SeedSequence:
    """Stream ``stream`` of the run's ``--seed`` (any whole number)."""
    return np.random.SeedSequence([int(seed) % (1 << 64), stream])


def poisson_inputs(spec, traffic: dict, seed: int,
                   count: int = DISTINCT_INPUTS) -> List[np.ndarray]:
    """``count`` input trains, (steps, batch, n_input) uint8, each input
    population at its own rate."""
    steps, batch = int(traffic["steps"]), int(traffic["batch"])
    rng = np.random.default_rng(seed_sequence(seed, 2))
    cols = spec.input_slices()
    out = []
    for _ in range(count):
        x = np.zeros((steps, batch, spec.n_input), np.uint8)
        for p in spec.inputs:
            a, b = cols[p.name]
            x[:, :, a:b] = rng.random((steps, batch, b - a)) < p.rate
        out.append(x)
    return out


class SimDriver:
    """One network, launched on a fixed set of inputs for the window."""

    def __init__(self, h):
        import jax
        import jax.numpy as jnp
        from repro.core.runtime import network_executable

        self.h = h
        t = h.traffic
        self.exe = network_executable(h.net, h.report)
        self.temporal = bool(t["temporal"])
        self.steps, self.batch = int(t["steps"]), int(t["batch"])
        self.inputs = poisson_inputs(h.spec, t, h.seed)
        # on the device in the type the program computes in, so a launch
        # is the program's own work and not a host-to-device copy
        self.trains = [jax.device_put(jnp.asarray(x, jnp.float32))
                       for x in self.inputs]
        self.launches = []          # spans of the launches done in the window
        self.ran = []               # and of the one that overran its end
        self.kept: Dict[int, list] = {}   # launch number -> its outputs
        self.window = (0.0, 0.0)

    def _launch(self, k: int):
        return self.exe.run(self.trains[k % len(self.trains)],
                            temporal=self.temporal)

    def warm(self) -> None:
        """The launch program compiles on its first call; the second shows
        that a further input of the same shape compiles nothing more."""
        for k in range(2):
            self._launch(k)

    def run_window(self, seconds: float, seed: int) -> None:
        spans = self.h.spans
        rng = np.random.default_rng(seed_sequence(seed, 3))
        t0 = time.perf_counter()
        self.window = (t0, t0 + seconds)
        k = 0
        while time.perf_counter() < self.window[1]:
            with spans.span("sim.launch", input=k % len(self.trains),
                            batch=self.batch, steps=self.steps) as s:
                outs = self._launch(k)
            if self.temporal:
                rec = self.exe.report.temporal[(self.batch, self.steps)]
                s.attrs["iterations"] = dict(rec.iterations)
                s.attrs["residual"] = sum(rec.residual.values())
            self.ran.append(s)
            if s.t1 > self.window[1]:
                break
            self.launches.append(s)
            # reservoir sampling: each launch is kept with equal chance
            n = len(self.launches)
            slot = n - 1 if n <= KEPT else int(rng.integers(0, n))
            if slot < KEPT:
                old = sorted(self.kept)[slot] if n > KEPT else None
                self.kept.pop(old, None)
                self.kept[n - 1] = outs
            k += 1

    def window_launches(self):
        return self.launches

    def replace_answers(self, produce: Callable) -> None:
        """Put ``produce(spikes) -> {population: train}`` in the program's
        place for every kept launch (the control)."""
        projs = self.h.spec.projs
        for n in self.kept:
            x = self.inputs[self.launches[n].attrs["input"]]
            trains = produce(x)
            self.kept[n] = [trains[e.post] for e in projs]

    def check(self) -> Dict[str, tuple]:
        """Every kept launch against the reference: spike mismatches over
        every projection's target train (limit 0), a window with no launch
        (limit 0), and for the temporal path the fixed point's residual
        flips over all of the window's launches (limit 0)."""
        spec = self.h.spec
        refs = {}
        mismatches = 0
        for n, outs in sorted(self.kept.items()):
            k = self.launches[n].attrs["input"]
            if k not in refs:
                refs[k] = reference.simulate(spec, self.inputs[k])
            # every projection's view of its target population
            for e, z in zip(spec.projs, outs):
                mismatches += int(np.count_nonzero(z != refs[k][e.post]))
        checks = {"spike_mismatches": (mismatches, 0),
                  "empty_window": (int(not self.launches), 0)}
        if self.temporal:
            checks["temporal_residual"] = (
                sum(s.attrs["residual"] for s in self.launches), 0)
        return checks

    def attempted_failed(self):
        return len(self.launches), 0

    def release(self) -> None:
        self.exe = None
        self.trains = None
