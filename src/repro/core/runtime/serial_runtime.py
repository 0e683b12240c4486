"""Serial-paradigm executor — event-driven semantics on the VPU path.

Walks the *compiled* serial artifacts exactly as the ARM core does
(paper §III-A): a spike from source j unlocks the master-population-table
entry, which points at j's address-list row, which points at j's block of
packed 32-bit synaptic rows; each row's weight is accumulated into the
synaptic input buffer slot selected by (delay, synapse type).

The TPU adaptation (DESIGN.md §2) expresses the same event-driven gather as
a data-parallel masked gather + segment-sum: per synaptic row r,
``contribution[r] = weight[r] * x_t[src[r]]`` scattered into the
(delay-slot, target) ring — identical arithmetic, identical spike trains.
The scatter is a single flat ``segment_sum`` over all ``B * R`` (batch, row)
pairs with batch-offset segment ids; the neural update runs through the
fused Pallas LIF kernel (:func:`repro.kernels.lif_update`).

Three kernel *forms* implement that step:

* :func:`serial_step` — the event form above; work ``O(B * R)`` but the
  scatter's locality degrades super-linearly in batch.
* :func:`serial_step_dense` — the dense fallback: the row arrays folded
  into a ``(d_slots, S, T)`` tensor so the whole update is one einsum plus
  a ring roll.  More MACs, each far cheaper, batch-scaling like the
  parallel paradigm — but the operand is dense storage, physically
  impossible for 100k-neuron sparse projections.
* :func:`serial_project_sparse` — the ELL gather form: synapses grouped
  into equal-length rows per (delay-slot, target) pair, each row
  *gathering* its sources' spike lanes (SpikeStream-style,
  :func:`ell_gather`, one XLA gather).  Work ``O(B * R)`` like the event
  form but with batch-contiguous reads instead of a scattered accumulate,
  so it scales linearly in batch; memory ``O(nnz)`` like the event form,
  so it is the only batch-friendly form sparse giants can run.

All weights are int8-magnitude integers, so every form accumulates
exactly in float32 and their spike trains are **bit-identical** — which
form runs is purely a throughput decision
(:class:`repro.core.cost_model.SerialBatchCostModel.choose_form`).

Each form is split into a *projection* half (:func:`serial_project` /
:func:`serial_project_dense`: delay-ring scatter -> this step's input
current) and the population-level LIF update, because in the application
graph several projections can converge on one population — their currents
sum before thresholding.  The ``serial_step*`` wrappers compose the two
halves exactly as before, so single-projection (chain) execution is
bit-identical to the pre-graph executor.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ...kernels.lif_update import lif_update
from ..layer import LIFParams, SNNLayer
from ..serial_compiler import SerialProgram, compile_serial, unpack_rows
from .reference import LIFState, init_state

#: Total ``lower_serial`` invocations (benchmarks assert executable caching
#: keeps this at one per layer per report).
LOWER_COUNT = 0


@dataclasses.dataclass
class SerialExecutable:
    """Flattened row arrays across all machine-graph cells."""

    n_source: int
    n_target: int
    delay_range: int
    row_weight: jnp.ndarray   # (R,) f32 signed weight
    row_delay: jnp.ndarray    # (R,) i32 in [1, D]
    row_src: jnp.ndarray      # (R,) i32 global source index
    row_tgt: jnp.ndarray      # (R,) i32 global target index
    lif: LIFParams


def lower_serial(program: SerialProgram, lif: LIFParams | None = None) -> SerialExecutable:
    """Decode packed rows of every cell into flat gather arrays."""
    global LOWER_COUNT
    LOWER_COUNT += 1
    ws, ds_, ss, ts = [], [], [], []
    for cell in program.cells:
        w, d, tgt_local = unpack_rows(cell.synaptic_rows)
        # reconstruct each row's source neuron from the address list
        row_start, row_len = cell.address_list[:, 0], cell.address_list[:, 1]
        src_local = np.repeat(np.arange(cell.src_size), row_len)
        ws.append(w)
        ds_.append(d)
        ss.append(src_local + cell.src_start)
        ts.append(tgt_local + cell.tgt_start)
    cat = lambda a, dt: jnp.asarray(np.concatenate(a) if a else np.zeros(0), dt)
    return SerialExecutable(
        n_source=program.n_source,
        n_target=program.n_target,
        delay_range=program.delay_range,
        row_weight=cat(ws, jnp.float32),
        row_delay=cat(ds_, jnp.int32),
        row_src=cat(ss, jnp.int32),
        row_tgt=cat(ts, jnp.int32),
        lif=lif or LIFParams(),
    )


@partial(
    jax.jit,
    static_argnames=("delay_range", "n_target", "interpret"),
)
def serial_project(
    exe_weight, exe_delay, exe_src, exe_tgt,
    ring: jnp.ndarray,   # (d_slots, B, n_target) f32 future input currents
    x_t: jnp.ndarray,    # (B, S)
    t: jnp.ndarray,
    *,
    delay_range: int,
    n_target: int,
    interpret: bool | None = None,
):
    """Event-form synaptic-current step of ONE projection.

    Scatters this timestep's presynaptic spikes through the delay ring and
    returns ``(ring', i_t)`` — the updated ring and the ``(B, n_target)``
    input current the target population consumes at ``t``.  The neural
    update lives with the *population* (:func:`repro.kernels.lif_update`),
    so multiple projections converging on one population sum their
    currents before thresholding.
    """
    d_slots = delay_range + 1
    batch = x_t.shape[0]
    # event-driven gather: row fires iff its source spiked this timestep
    fired = x_t[:, exe_src]                      # (B, R)
    contrib = fired * exe_weight[None, :]        # (B, R)
    slot = (t + exe_delay) % d_slots             # (R,)
    seg = slot * n_target + exe_tgt              # (R,) ring-flat segment ids
    # one flat segment_sum over all (batch, row) pairs: batch b's rows are
    # offset into their own block of d_slots * n_target segments
    seg_flat = (
        jnp.arange(batch, dtype=jnp.int32)[:, None] * (d_slots * n_target)
        + seg[None, :]
    ).reshape(-1)                                # (B*R,)
    updates = jax.ops.segment_sum(
        contrib.reshape(-1), seg_flat, num_segments=batch * d_slots * n_target
    )                                            # (B*slots*T,)
    ring = ring + updates.reshape(-1, d_slots, n_target).transpose(1, 0, 2)
    i_t = ring[t % d_slots]
    ring = ring.at[t % d_slots].set(0.0)
    return ring, i_t


@partial(
    jax.jit,
    static_argnames=("delay_range", "n_target", "alpha", "v_th", "interpret"),
)
def serial_step(
    exe_weight, exe_delay, exe_src, exe_tgt,
    state: LIFState,
    x_t: jnp.ndarray,    # (B, S)
    t: jnp.ndarray,
    *,
    delay_range: int,
    n_target: int,
    alpha: float,
    v_th: float,
    interpret: bool | None = None,
):
    ring, i_t = serial_project(
        exe_weight, exe_delay, exe_src, exe_tgt, state.ring, x_t, t,
        delay_range=delay_range, n_target=n_target, interpret=interpret,
    )
    # fused Pallas LIF update operates (neurons, batch)
    v_new, z_new = lif_update(
        i_t.T, state.v.T, state.z.T, alpha=alpha, v_th=v_th, interpret=interpret
    )
    return LIFState(v=v_new.T, z=z_new.T, ring=ring), z_new.T


def dense_serial_weights(exe: SerialExecutable) -> np.ndarray:
    """Fold the flat row arrays into a ``(d_slots, S, T)`` dense tensor.

    Slot ``d`` holds the delay-``d`` weights (slot 0 is all zero — delays
    are >= 1), so ``x_t @ W[d]`` is exactly the sum the event form
    scatters for delay ``d``.
    """
    d_slots = exe.delay_range + 1
    w = np.zeros((d_slots, exe.n_source, exe.n_target), np.float32)
    np.add.at(
        w,
        (
            np.asarray(exe.row_delay),
            np.asarray(exe.row_src),
            np.asarray(exe.row_tgt),
        ),
        np.asarray(exe.row_weight),
    )
    return w


@partial(
    jax.jit,
    static_argnames=("delay_range", "n_target", "interpret"),
)
def serial_project_dense(
    w_dense,             # (d_slots, S, T) f32 per-delay-slot weights
    ring: jnp.ndarray,   # (d_slots, B, n_target) f32 future input currents
    x_t: jnp.ndarray,    # (B, S)
    t: jnp.ndarray,
    *,
    delay_range: int,
    n_target: int,
    interpret: bool | None = None,
):
    """Dense-fallback synaptic-current step — same ring, same currents.

    ``upd[d] = x_t @ W[d]`` is the total delay-``d`` contribution; rolling
    by ``t`` lands it in ring slot ``(t + d) % d_slots``, exactly where the
    event form's segment ids point.  Delay-0 weights are structurally zero,
    so the current slot is read before anything lands in it — the same
    delays >= 1 ordering the event form relies on.
    """
    d_slots = delay_range + 1
    upd = jnp.einsum("bs,dst->dbt", x_t, w_dense)    # (d_slots, B, T)
    ring = ring + jnp.roll(upd, t, axis=0)
    i_t = ring[t % d_slots]
    ring = ring.at[t % d_slots].set(0.0)
    return ring, i_t


@partial(
    jax.jit,
    static_argnames=("delay_range", "n_target", "alpha", "v_th", "interpret"),
)
def serial_step_dense(
    w_dense,             # (d_slots, S, T) f32 per-delay-slot weights
    state: LIFState,
    x_t: jnp.ndarray,    # (B, S)
    t: jnp.ndarray,
    *,
    delay_range: int,
    n_target: int,
    alpha: float,
    v_th: float,
    interpret: bool | None = None,
):
    """Dense-fallback serial step — same carry, same outputs, all matmul."""
    ring, i_t = serial_project_dense(
        w_dense, state.ring, x_t, t,
        delay_range=delay_range, n_target=n_target, interpret=interpret,
    )
    # fused Pallas LIF update operates (neurons, batch)
    v_new, z_new = lif_update(
        i_t.T, state.v.T, state.z.T, alpha=alpha, v_th=v_th, interpret=interpret
    )
    return LIFState(v=v_new.T, z=z_new.T, ring=ring), z_new.T


def sparse_serial_operands(exe: SerialExecutable):
    """Group the flat row arrays into ELL form for the sparse kernel.

    One ELL row per ``(delay_slot, target)`` pair — row id ``delay *
    n_target + target`` — holding that pair's source indices and weights,
    padded to the longest row with weight-0 / index-0 lanes.  The gather
    ``out[row] = sum_l w[row, l] * x[idx[row, l]]`` then computes exactly
    the sum the event form scatters into ring slot ``(t + delay) %
    d_slots`` at target ``target``; reshaping rows to ``(d_slots, T)`` and
    rolling by ``t`` reuses the dense form's ring update verbatim.

    Returns ``(ell_val, ell_idx)``: ``(d_slots * n_target, L)`` f32/i32
    host-side numpy arrays (lowered once per executable, cached by the
    executor next to the dense operand).
    """
    d_slots = exe.delay_range + 1
    T = exe.n_target
    w = np.asarray(exe.row_weight, np.float32)
    dly = np.asarray(exe.row_delay, np.int64)
    src = np.asarray(exe.row_src, np.int64)
    tgt = np.asarray(exe.row_tgt, np.int64)
    n_rows = d_slots * T
    row_id = dly * T + tgt
    counts = np.bincount(row_id, minlength=n_rows)
    L = max(1, int(counts.max()) if counts.size else 1)
    order = np.argsort(row_id, kind="stable")
    starts = np.cumsum(counts) - counts               # first slot of each row
    lane = np.arange(row_id.size) - np.repeat(starts, counts)
    ell_val = np.zeros((n_rows, L), np.float32)
    ell_idx = np.zeros((n_rows, L), np.int32)
    ell_val[row_id[order], lane] = w[order]
    ell_idx[row_id[order], lane] = src[order]
    return ell_val, ell_idx


def ell_gather(
    ell_val: jnp.ndarray,   # (R, L) f32 weights, 0 in padding lanes
    ell_idx: jnp.ndarray,   # (R, L) i32 source indices, 0 in padding lanes
    x: jnp.ndarray,         # (S, B) f32 presynaptic spikes
) -> jnp.ndarray:
    """``out[r, b] = sum_l ell_val[r, l] * x[ell_idx[r, l], b]``.  (R, B) f32.

    One XLA gather plus a row-axis sum, on every backend: the TPU's
    kernel compiler refuses an in-kernel vector gather, and a kernel that
    kept the whole ``(S, B)`` train resident would not fit VMEM at 10^5
    sources.  Padding lanes carry weight 0, so their gathered (row-0)
    spikes never contribute.  All weights are int8-magnitude integers and
    spikes are 0/1, so the f32 accumulation is exact and
    order-independent — the property that keeps the sparse form
    bit-identical to the event and dense forms.
    """
    gathered = x[ell_idx.reshape(-1)].reshape(*ell_idx.shape, x.shape[1])
    return (gathered * ell_val[..., None]).sum(axis=1)


@partial(
    jax.jit,
    static_argnames=("delay_range", "n_target", "interpret"),
)
def serial_project_sparse(
    ell_val,             # (d_slots * T, L) f32 ELL weights
    ell_idx,             # (d_slots * T, L) i32 ELL source indices
    ring: jnp.ndarray,   # (d_slots, B, n_target) f32 future input currents
    x_t: jnp.ndarray,    # (B, S)
    t: jnp.ndarray,
    *,
    delay_range: int,
    n_target: int,
    interpret: bool | None = None,
):
    """Sparse (ELL gather) synaptic-current step — same ring, same currents.

    Each ELL row gathers and accumulates one ``(delay, target)`` pair's
    contribution for the whole batch (:func:`ell_gather`);
    reshaping to ``(d_slots, B, T)`` and rolling by ``t`` lands delay-``d``
    sums in ring slot ``(t + d) % d_slots``, exactly where the event form's
    segment ids point.  Delay-0 rows are structurally empty (delays >= 1),
    so the current slot is read before anything lands in it.
    """
    d_slots = delay_range + 1
    out = ell_gather(ell_val, ell_idx, x_t.T)
    upd = out.reshape(d_slots, n_target, -1).transpose(0, 2, 1)  # (d,B,T)
    ring = ring + jnp.roll(upd, t, axis=0)
    i_t = ring[t % d_slots]
    ring = ring.at[t % d_slots].set(0.0)
    return ring, i_t


@partial(
    jax.jit,
    static_argnames=("delay_range", "n_target", "alpha", "v_th", "interpret"),
)
def serial_step_sparse(
    ell_val,             # (d_slots * T, L) f32 ELL weights
    ell_idx,             # (d_slots * T, L) i32 ELL source indices
    state: LIFState,
    x_t: jnp.ndarray,    # (B, S)
    t: jnp.ndarray,
    *,
    delay_range: int,
    n_target: int,
    alpha: float,
    v_th: float,
    interpret: bool | None = None,
):
    """Sparse serial step — same carry, same outputs, gather + LIF."""
    ring, i_t = serial_project_sparse(
        ell_val, ell_idx, state.ring, x_t, t,
        delay_range=delay_range, n_target=n_target, interpret=interpret,
    )
    # fused Pallas LIF update operates (neurons, batch)
    v_new, z_new = lif_update(
        i_t.T, state.v.T, state.z.T, alpha=alpha, v_th=v_th, interpret=interpret
    )
    return LIFState(v=v_new.T, z=z_new.T, ring=ring), z_new.T


def run_serial(
    layer: SNNLayer,
    spikes: np.ndarray,
    lif: LIFParams | None = None,
    program: SerialProgram | None = None,
    interpret: bool | None = None,
) -> np.ndarray:
    program = program or compile_serial(layer)
    exe = lower_serial(program, lif or layer.lif)
    T, B, _ = spikes.shape
    state = init_state(B, exe.n_target, exe.delay_range)

    def step(carry, x_t):
        state, t = carry
        state, z = serial_step(
            exe.row_weight, exe.row_delay, exe.row_src, exe.row_tgt,
            state, x_t, t,
            delay_range=exe.delay_range, n_target=exe.n_target,
            alpha=exe.lif.alpha, v_th=exe.lif.v_th, interpret=interpret,
        )
        return (state, t + 1), z

    (_, _), zs = jax.lax.scan(
        step, (state, jnp.int32(0)), jnp.asarray(spikes, jnp.float32)
    )
    return np.asarray(zs)
