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
* :func:`serial_project_spike` — the **spike-driven** form, SpiNNaker's
  serial paradigm taken literally: each step it finds the sources that
  fired, gathers their synaptic rows (padded to the longest) and
  scatter-adds each synapse's weight into its target's ring slot
  ``t + delay``.  Its work follows the spikes (``O(B * S)`` to find them,
  then the rows of those that fired), not ``B * R``; a step with more
  spikes than one pass holds takes further passes, so no spike is ever
  dropped.

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

from ... import tracing
from ...kernels.lif_update import lif_update
from ..layer import LIFParams, SNNLayer
from ..serial_compiler import SerialProgram, compile_serial, decode_rows
from .reference import LIFState, init_state

@dataclasses.dataclass
class SerialExecutable:
    """Flattened row arrays across all machine-graph cells.

    The arrays are host (numpy) arrays: each kernel form builds its own
    device operands from them (the executor caches those), so a
    projection that runs one form holds only that form's operands on the
    device."""

    n_source: int
    n_target: int
    delay_range: int
    row_weight: np.ndarray    # (R,) f32 signed weight
    row_delay: np.ndarray     # (R,) i32 in [0, D]
    row_src: np.ndarray       # (R,) i32 global source index
    row_tgt: np.ndarray       # (R,) i32 global target index
    lif: LIFParams


def lower_serial(program: SerialProgram, lif: LIFParams | None = None) -> SerialExecutable:
    """Decode packed rows of every cell into flat gather arrays."""
    tracing.count("lower.serial")
    cells = program.cells
    if cells:
        w, d, tgt_local = decode_rows(
            np.concatenate([c.synaptic_rows for c in cells])
        )
        sizes = [c.synaptic_rows.size for c in cells]
        # each row's source neuron from its cell's address list
        src = np.concatenate([
            np.repeat(np.arange(c.src_start, c.src_start + c.src_size,
                                dtype=np.int32), c.address_list[:, 1])
            for c in cells
        ])
        tgt = tgt_local + np.repeat(
            np.array([c.tgt_start for c in cells], np.int32), sizes)
    else:
        w = d = src = tgt = np.zeros(0)
    return SerialExecutable(
        n_source=program.n_source,
        n_target=program.n_target,
        delay_range=program.delay_range,
        row_weight=np.asarray(w, np.float32),
        row_delay=np.asarray(d, np.int32),
        row_src=np.asarray(src, np.int32),
        row_tgt=np.asarray(tgt, np.int32),
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


# -- the spike-driven form ----------------------------------------------------

#: Synapse slots of a full pass of the spike-driven form, per lane: it
#: takes up as many spikes as padded rows fit (at least one).
FULL_SLOTS = 1 << 14
#: Synapse slots of a light pass, taken where the step's remaining spikes
#: fit it (most steps, away from volleys).
LIGHT_SLOTS = 1 << 12


@dataclasses.dataclass(frozen=True)
class SpikeLayout:
    """Static facts of one projection's spike-driven form.

    Each synapse is one int32 word, SpiNNaker's packed synaptic row:
    ``weight << (tgt_bits + delay_bits) | delay << tgt_bits | target``,
    the weight signed in the top bits.  A source's row is padded to the
    projection's longest, ``row_len`` words.  ``full`` and ``light`` are
    the spikes a full and a light pass take up per lane.
    """

    tgt_bits: int
    delay_bits: int
    row_len: int
    full: int
    light: int


def _bits(n: int) -> int:
    return max(1, int(n).bit_length())


def spike_layout(exe: SerialExecutable) -> SpikeLayout | None:
    """The spike-driven form's layout of ``exe``, or ``None`` where a
    synapse does not fit one int32 word (a weight that is not an integer,
    or target, delay and weight wider than 32 bits).

    The capacities come from the projection's shapes alone: a full pass
    takes up as many spikes as rows of ``row_len`` fit
    :data:`FULL_SLOTS`, a light pass :data:`LIGHT_SLOTS`.
    """
    w = np.asarray(exe.row_weight)
    n_rows = int(w.size)
    tgt_bits = _bits(max(0, exe.n_target - 1))
    delay_bits = _bits(exe.delay_range)
    if n_rows:
        w_max = int(np.abs(w).max())
        if not np.array_equal(w, np.round(w)):
            return None
        row_len = int(np.bincount(exe.row_src, minlength=1).max())
    else:
        w_max, row_len = 0, 1
    if tgt_bits + delay_bits + _bits(w_max) + 1 > 32 or n_rows >= 2 ** 31:
        return None
    full = max(1, FULL_SLOTS // row_len)
    return SpikeLayout(tgt_bits, delay_bits, row_len, full,
                       min(full, max(1, LIGHT_SLOTS // row_len)))


def spike_serial_operands(exe: SerialExecutable, layout: SpikeLayout):
    """``(row_len, rows)``: each source's synapse count, ``(S,)`` int32,
    and its row of synapses, each packed into one int32 word
    (:class:`SpikeLayout`), padded with zeros to ``(S, row_len)``.  Host
    numpy arrays."""
    src = np.asarray(exe.row_src)
    # a stable radix sort where the source index fits 16 bits
    key = src.astype(np.uint16) if exe.n_source <= 1 << 16 else src
    order = np.argsort(key, kind="stable")
    counts = np.bincount(src, minlength=exe.n_source)
    first = np.cumsum(counts) - counts
    src = src[order]
    shift = layout.tgt_bits + layout.delay_bits
    words = np.asarray(exe.row_weight)[order].astype(np.int32) << shift
    words |= np.asarray(exe.row_delay)[order].astype(np.int32) << layout.tgt_bits
    words |= np.asarray(exe.row_tgt)[order].astype(np.int32)
    rows = np.zeros((exe.n_source, layout.row_len), np.int32)
    rows[src, np.arange(src.size) - first[src]] = words
    return counts.astype(np.int32), rows


def serial_project_spike(
    row_len,             # (S,) i32 synapses of each source's row
    rows,                # (S, L) i32 packed synapses, rows padded to L
    ring: jnp.ndarray,   # (d_slots, B, n_target) i32 future input currents
    x_t: jnp.ndarray,    # (B, S) spikes (0/1)
    t: jnp.ndarray,
    *,
    n_target: int,
    layout: SpikeLayout,
):
    """Spike-driven synaptic-current step of ONE projection.

    Returns ``(ring', i_t, (events, passes))``: the ring with this step's
    synapses routed into slot ``(t + delay) % d_slots`` and the current
    slot cleared, the ``(B, n_target)`` int32 current read from slot ``t``
    (after routing, so a delay-0 synapse lands in the step it is routed
    in), and the step's synaptic events and passes.

    A pass takes up the next ``light`` (where the step's remaining spikes
    fit them) or ``full`` of the (lane, source) spikes in rank order — a
    search of the spikes' running count finds them — gathers their rows
    whole and scatter-adds every synapse into the ring.  Passes repeat
    until every spike is routed, so a step's work follows its spikes and
    none is dropped.
    """
    d_slots, batch = ring.shape[0], ring.shape[1]
    n_source = x_t.shape[1]
    lanes_src = batch * n_source
    size = d_slots * batch * n_target
    # running count of the step's spikes, lane-major: spike of rank k
    # (from 0) sits at the first position whose count exceeds k
    count = jnp.cumsum((x_t > 0).reshape(-1).astype(jnp.int32))
    n_fired = count[-1]
    col = jnp.arange(layout.row_len, dtype=jnp.int32)
    shift = layout.tgt_bits + layout.delay_bits
    t_mask = (1 << layout.tgt_bits) - 1
    d_mask = (1 << layout.delay_bits) - 1

    def route(sc):
        """A pass over the spikes of ranks [c0, c0 + sc)."""
        k = jnp.arange(sc, dtype=jnp.int32)
        method = "compare_all" if sc * lanes_src <= 1 << 24 else "scan"

        def go(state):
            ring_f, c0 = state
            live = k < n_fired - c0
            ids = jnp.where(
                live, jnp.searchsorted(count, c0 + k + 1, method=method), 0)
            src = ids % n_source
            word = rows[src]                                   # (sc, L)
            valid = live[:, None] & (col[None, :] < row_len[src][:, None])
            slot = (t + ((word >> layout.tgt_bits) & d_mask)) % d_slots
            flat = (slot * batch + (ids // n_source)[:, None]) * n_target \
                + (word & t_mask)
            ring_f = ring_f.at[jnp.where(valid, flat, size).reshape(-1)].add(
                (word >> shift).reshape(-1), mode="drop")
            return ring_f, jnp.sum(jnp.where(live, row_len[src], 0))
        return go

    full = min(lanes_src, batch * layout.full)
    light = min(full, batch * layout.light)

    def step(state):
        ring_f, c0, passes, events = state
        is_light = n_fired - c0 <= light
        if light < full:
            ring_f, n = jax.lax.cond(is_light, route(light), route(full),
                                     (ring_f, c0))
        else:
            ring_f, n = route(full)((ring_f, c0))
        return (ring_f, c0 + jnp.where(is_light, light, full), passes + 1,
                events + n)

    zero = jnp.int32(0)
    ring_f, _, passes, events = jax.lax.while_loop(
        lambda s: s[1] < n_fired, step,
        (ring.reshape(-1), zero, zero, zero),
    )
    ring = ring_f.reshape(d_slots, batch, n_target)
    now = t % d_slots
    i_t = ring[now]
    ring = ring.at[now].set(0)
    return ring, i_t, (events, passes)
