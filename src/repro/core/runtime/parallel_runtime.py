"""Parallel-paradigm executor — the MAC/MXU path (paper §III-B).

Per timestep:

1. **Dominant PE** — maintains the input-spike ring (last ``delay_range``
   spike vectors) and assembles the *stacked input buffer* through the
   input merging table: column c of the buffer is
   ``x[t - delay(c)][source(c)]``, read via the *reversed order* ring
   indices.  The ring is stored ``(depth, n_source, batch)`` so the read
   is a single flat row ``take`` on the ``(depth * n_source, batch)``
   view — one gathered axis, which XLA lowers as an efficient
   take-along-axis instead of a mixed-basis advanced-indexing gather.
2. **Subordinate PEs** — one int8 x int8 -> int32 matmul of the optimized
   weight-delay-map with the stacked input on the MAC array.  On TPU this
   is the Pallas MXU kernel :func:`repro.kernels.spike_wdm_matmul`.
3. Fused LIF update (:func:`repro.kernels.lif_update`).

Bit-identical to the dense oracle: every accumulation is an exact int32.

The ring depth is clamped to ``max(1, delay_range)`` so the degenerate
``delay_range == 0`` program (an empty layer) executes instead of dividing
by zero in the ring index arithmetic.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ... import tracing
from ...kernels.lif_update import lif_update
from ...kernels.spike_wdm_matmul import spike_wdm_matmul
from ..layer import LIFParams, SNNLayer
from ..parallel_compiler import OptFlags, ParallelProgram, compile_parallel
from .reference import LIFState, init_state

@dataclasses.dataclass
class ParallelExecutable:
    n_source: int
    n_target: int
    delay_range: int
    wdm_stack: jnp.ndarray    # (n_target, C) int8 — slices concatenated
    col_source: jnp.ndarray   # (C,) i32 input-merging-table: column -> source
    col_delay: jnp.ndarray    # (C,) i32 reversed-order: column -> delay
    lif: LIFParams
    #: Some column has delay 0 (a back-edge): the ring is one slot deeper
    #: and this step's spikes are written before the read.
    zero_delay: bool = False

    @property
    def ring_depth(self) -> int:
        """Spike-history ring depth; >= 1 even for degenerate programs."""
        return parallel_ring_depth(self.delay_range, self.zero_delay)


def parallel_ring_depth(delay_range: int, zero_delay: bool) -> int:
    """Slots of a parallel projection's spike-history ring: ``delay_range``
    (>= 1), one more when a delay-0 column reads this step's spikes."""
    return delay_range + 1 if zero_delay else max(1, delay_range)


def lower_parallel(
    program: ParallelProgram, lif: LIFParams | None = None
) -> ParallelExecutable:
    """Concatenate the optimized WDM slices into one (T x C) MXU operand."""
    tracing.count("lower.parallel")
    mats, srcs, dls = [], [], []
    for sl in program.slices:
        n_cols = len(sl.col_sources)
        if n_cols == 0:
            continue
        mats.append(sl.matrix[: program.n_target, :n_cols])
        srcs.append(sl.col_sources)
        dls.append(np.full(n_cols, sl.delay, dtype=np.int64))
    if mats:
        wdm = np.concatenate(mats, axis=1).astype(np.int8)
        col_source = np.concatenate(srcs)
        col_delay = np.concatenate(dls)
    else:
        wdm = np.zeros((program.n_target, 0), np.int8)
        col_source = np.zeros(0, np.int64)
        col_delay = np.zeros(0, np.int64)
    return ParallelExecutable(
        n_source=program.n_source,
        n_target=program.n_target,
        delay_range=program.delay_range,
        wdm_stack=jnp.asarray(wdm),
        col_source=jnp.asarray(col_source, jnp.int32),
        col_delay=jnp.asarray(col_delay, jnp.int32),
        lif=lif or LIFParams(),
        zero_delay=bool(col_delay.size and col_delay.min() == 0),
    )


@partial(jax.jit, static_argnames=("interpret", "write_first"))
def parallel_project(
    wdm_stack, col_source, col_delay,
    x_hist: jnp.ndarray,      # (ring_depth, S, B) int8 spike history ring
    x_t: jnp.ndarray,         # (B, S) f32 spikes at t
    t: jnp.ndarray,
    *,
    interpret: bool | None = None,
    write_first: bool = False,
):
    """Dominant-PE + MXU half of ONE projection.

    Returns ``(x_hist', i_t)`` — the spike-history ring with ``x_t``
    written in, and the ``(n_target, B)`` input current the target
    population consumes at ``t``.  The LIF update lives with the
    population so converging projections sum their currents first.
    ``write_first`` (a program with delay-0 columns, whose ring is one
    slot deeper) writes ``x_t`` before the read, so delay 0 reads it.
    """
    # the allocated ring IS the truth for the depth (clamped >= 1 at
    # allocation via ring_depth), so the index arithmetic cannot drift
    d, n_source = x_hist.shape[0], x_hist.shape[1]
    if write_first:
        x_hist = x_hist.at[t % d].set(x_t.T.astype(jnp.int8))
    # dominant PE: stacked input via merging table + reversed order; one
    # flat row gather on the (depth * n_source, batch) ring view
    slot = (t - col_delay) % d                       # (C,)
    stacked = jnp.take(
        x_hist.reshape(d * n_source, -1), slot * n_source + col_source, axis=0
    )                                                # (C, B) int8
    i_t = spike_wdm_matmul(
        wdm_stack, stacked, interpret=interpret
    ).astype(jnp.float32)                            # (T, B)
    if not write_first:
        # write x_t into the history ring AFTER the read (delays are >= 1)
        x_hist = x_hist.at[t % d].set(x_t.T.astype(jnp.int8))
    return x_hist, i_t


@partial(jax.jit, static_argnames=("alpha", "v_th", "interpret"))
def parallel_step(
    wdm_stack, col_source, col_delay,
    x_hist: jnp.ndarray,      # (max(1, D), S, B) int8 spike history ring
    state: LIFState,          # .ring unused here (kept for API parity)
    x_t: jnp.ndarray,         # (B, S) f32 spikes at t
    t: jnp.ndarray,
    *,
    alpha: float,
    v_th: float,
    interpret: bool | None = None,
):
    x_hist, i_t = parallel_project(
        wdm_stack, col_source, col_delay, x_hist, x_t, t, interpret=interpret
    )
    # fused LIF update operates (neurons, batch)
    v_new, z_new = lif_update(
        i_t, state.v.T, state.z.T, alpha=alpha, v_th=v_th, interpret=interpret
    )
    new_state = LIFState(v=v_new.T, z=z_new.T, ring=state.ring)
    return x_hist, new_state, z_new.T


def run_parallel(
    layer: SNNLayer,
    spikes: np.ndarray,       # (T, B, S) 0/1
    lif: LIFParams | None = None,
    program: ParallelProgram | None = None,
    opts: OptFlags = OptFlags(),
    interpret: bool | None = None,
) -> np.ndarray:
    program = program or compile_parallel(layer, opts=opts)
    exe = lower_parallel(program, lif or layer.lif)
    T, B, _ = spikes.shape
    state = init_state(B, exe.n_target, 0)
    x_hist = jnp.zeros((exe.ring_depth, exe.n_source, B), jnp.int8)

    def step(carry, x_t):
        x_hist, state, t = carry
        x_hist, state, z = parallel_step(
            exe.wdm_stack, exe.col_source, exe.col_delay,
            x_hist, state, x_t, t,
            alpha=exe.lif.alpha, v_th=exe.lif.v_th, interpret=interpret,
        )
        return (x_hist, state, t + 1), z

    (_, _, _), zs = jax.lax.scan(
        step, (x_hist, state, jnp.int32(0)), jnp.asarray(spikes, jnp.float32)
    )
    return np.asarray(zs)
