"""Fused whole-network executor — one jitted scan for the application graph.

On SpiNNaker2 every population advances together each timestep: the chip
runs a lockstep per-timestep pipeline across all PEs (arXiv 1911.02385),
whatever paradigm each projection's PEs execute.  This module mirrors that
structure on the accelerator:

* :func:`get_layer_executable` lowers a :class:`CompiledLayer`'s program
  once and caches the result on the compiled projection (keyed by program
  identity — the executable lives exactly as long as the program it was
  lowered from), so repeated runs never re-lower.
* :class:`NetworkExecutable` executes the **application graph** of
  :class:`~repro.core.layer.SNNNetwork` — populations as vertices,
  projections as edges — in a **single jitted ``jax.lax.scan`` over
  timesteps**.  Within a timestep, forward projections cascade in the
  graph's topological order; **back-edges** (self-loops and projections
  onto earlier populations) read their source population's spikes from a
  one-step-delayed **feedback ring** carried in the scan state, so a
  spike crossing a back-edge of synaptic delay ``d`` arrives ``d + 1``
  steps after emission.  A pure feed-forward chain takes exactly the
  pre-graph code path (single in-edge per population, empty feedback
  ring) and is bit-identical to it.

Execution is factored per the graph: each projection contributes a
*synaptic current* through its paradigm's machinery
(:func:`~repro.core.runtime.serial_runtime.serial_project` /
:func:`~repro.core.runtime.parallel_runtime.parallel_project`); a
population sums the currents of all its in-projections and runs ONE fused
LIF update (:func:`repro.kernels.lif_update`).  All weights are
int8-magnitude integers, so the sums are exact in float32 and converging
projections stay bit-exact.

Batched and sharded execution (see ``docs/architecture.md``):

* :meth:`NetworkExecutable.run_device` — the fused path: one scan whose
  per-step kernels batch internally over the request axis.
* :meth:`NetworkExecutable.run_batched` — the vmapped path: one scan per
  request, ``jax.vmap``-ed over the request axis, ``valid_steps`` masking
  preserved per lane.  Bit-identical to the fused path (integer
  accumulation), but lets XLA batch each request's program independently.
* Serial projections pick between the event-driven ``segment_sum`` form,
  the ELL gather-accumulate **sparse** form, and the dense matmul
  fallback per launch batch
  (:meth:`repro.core.cost_model.SerialBatchCostModel.choose_form`); the
  choice is recorded in ``CompileReport.serial_forms`` and never changes
  outputs.  Projections too large to materialize densely (over the cost
  model's element cap) never pick dense — the sparse form is what lets
  20k+-neuron, sub-percent-density graphs run through the same scan.
* Spike state crossing timesteps is **int8** end-to-end: the
  per-population previous-spike vectors and the back-edge feedback ring
  are carried as int8 (spikes are exactly 0/1, so the casts are
  bit-exact), matching the parallel paradigm's int8 spike-history rings
  and cutting carried-state memory traffic 4x.
* :meth:`NetworkExecutable.shard` places the lowered weight/delay
  operands by the logical-axis rules in
  :mod:`repro.distributed.sharding` (``snn_rules``: batch -> data,
  neurons -> model); on a single device it is the identity fallback.

The program builds its own zero carry: the scan state (membrane
potentials, delay rings, spike-history rings, feedback ring) is made
inside the jitted launch program, where the zeros fuse into it and XLA
allocates and updates the loop's buffers in place, so a launch does no
device work on the host before its dispatch.  The ``carry.traced``
counter of :mod:`repro.tracing` counts each time a zero state is traced:
once per new jit entry, never on a warm launch.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ... import tracing
from ...distributed import sharding as shardlib
from ...kernels.iaf_psc_exp import iaf_layout, iaf_psc_exp
from ...kernels.lif_update import lif_update
from ..cost_model import DEFAULT_SERIAL_BATCH_COST, SerialBatchCostModel
from ..layer import IafFixed, LIFParams, SNNNetwork, is_iaf
from ..parallel_compiler import ParallelProgram
from ..serial_compiler import SerialProgram
from ..switching import CompiledLayer, CompileReport
from .parallel_runtime import (
    ParallelExecutable,
    lower_parallel,
    parallel_project,
    parallel_ring_depth,
)
from .serial_runtime import (
    SerialExecutable,
    SpikeLayout,
    dense_serial_weights,
    lower_serial,
    serial_project,
    serial_project_dense,
    serial_project_sparse,
    serial_project_spike,
    sparse_serial_operands,
    spike_layout,
    spike_serial_operands,
)
from .temporal_runtime import (
    TemporalReport,
    choose_temporal_mode,
    temporal_lif,
    temporal_project_dense,
    temporal_project_sparse,
)


def get_layer_executable(
    compiled: CompiledLayer, lif: LIFParams | None = None
):
    """Lower ``compiled.program`` once; reuse the cached executable after.

    The cache is invalidated (re-lowered) if it was built for different
    LIF parameters than the ones requested now.
    """
    lif = lif or LIFParams()
    exe = compiled.executable
    if exe is not None and exe.lif == lif:
        return exe
    prog = compiled.program
    if isinstance(prog, SerialProgram):
        exe = lower_serial(prog, lif)
    elif isinstance(prog, ParallelProgram):
        exe = lower_parallel(prog, lif)
    else:  # pragma: no cover
        raise TypeError(type(prog))
    compiled.executable = exe
    return exe


@dataclasses.dataclass(frozen=True)
class LayerMeta:
    """Static (hashable) per-projection facts baked into the jitted scan.

    ``alpha``/``v_th`` are the *target population's* effective LIF
    parameters (for a chain: the layer's own ``lif``, as before).
    """

    paradigm: str        # "serial" | "parallel"
    n_source: int
    n_target: int
    delay_range: int
    alpha: float
    v_th: float
    #: Event volume: synaptic rows (serial) / WDM columns (parallel); feeds
    #: the serial dense-fallback crossover decision.
    n_rows: int = 0
    #: Parallel: some column has delay 0 (a back-edge), so the spike
    #: history is written before it is read, one slot deeper.
    zero_delay: bool = False
    #: Serial: the spike-driven form's word layout and per-lane pass
    #: capacities, or ``None`` where the synapses do not pack.
    spike: Optional[SpikeLayout] = None

    @property
    def ring_depth(self) -> int:
        """Spike-history ring depth; >= 1 even for degenerate programs."""
        return parallel_ring_depth(self.delay_range, self.zero_delay)


@dataclasses.dataclass(frozen=True)
class GraphPlan:
    """Static (hashable) application-graph structure baked into the scan.

    Population indices are the network's *declared* indices; only the
    iteration order (``update_order``) is topological.  Input populations
    carry dummy LIF constants (they have no neural update — their
    "spikes" are slices of the external train: input population
    ``input_pops[k]`` reads columns ``input_slices[k]`` of the
    concatenated ``(T, B, n_input)`` train, declared order).
    """

    pop_sizes: Tuple[int, ...]
    input_pops: Tuple[int, ...]           # declared indices of input pops
    input_slices: Tuple[Tuple[int, int], ...]  # per input pop: train columns
    update_order: Tuple[int, ...]         # non-input pops, topological order
    pop_alpha: Tuple[float, ...]
    pop_vth: Tuple[float, ...]
    in_edges: Tuple[Tuple[int, ...], ...]  # per pop: in-projection indices
    proj_src: Tuple[int, ...]             # per projection: source pop
    proj_tgt: Tuple[int, ...]             # per projection: target pop
    proj_back: Tuple[bool, ...]           # per projection: back-edge?
    back_sources: Tuple[int, ...]         # pops carried in the feedback ring
    #: per pop: the fixed-point constants of an ``iaf_psc_exp`` population,
    #: ``None`` for Eq. 1 (empty: every population runs Eq. 1)
    pop_iaf: Tuple[Optional[IafFixed], ...] = ()

    def iaf(self, p: int) -> Optional[IafFixed]:
        return self.pop_iaf[p] if self.pop_iaf else None


def _graph_plan(net: SNNNetwork) -> GraphPlan:
    """Extract the static execution plan from the application graph."""
    n = len(net.populations)
    input_pops = net.input_indices
    input_set = frozenset(input_pops)
    update_order = tuple(p for p in net.topo_order if p not in input_set)
    alpha, vth = [0.0] * n, [1.0] * n
    iaf: List[Optional[IafFixed]] = [None] * n
    for p in update_order:
        lif = net.population_lif(p)
        if is_iaf(lif):
            iaf[p] = lif.fixed()
        else:
            alpha[p], vth[p] = float(lif.alpha), float(lif.v_th)
    endpoints = net.endpoints
    proj_src = tuple(net.population_index(pre) for pre, _ in endpoints)
    return GraphPlan(
        pop_sizes=tuple(p.size for p in net.populations),
        input_pops=input_pops,
        input_slices=net.input_slices,
        update_order=update_order,
        pop_alpha=tuple(alpha),
        pop_vth=tuple(vth),
        in_edges=tuple(net.in_edges),
        proj_src=proj_src,
        proj_tgt=tuple(
            net.population_index(post) for _, post in endpoints
        ),
        proj_back=tuple(
            i in net.back_edges for i in range(len(endpoints))
        ),
        back_sources=tuple(sorted({proj_src[i] for i in net.back_edges})),
        pop_iaf=tuple(iaf) if any(c is not None for c in iaf) else (),
    )


def _chain_plan(metas: Tuple[LayerMeta, ...]) -> GraphPlan:
    """The feed-forward chain plan (for handles built without a network)."""
    n = len(metas) + 1
    return GraphPlan(
        pop_sizes=(metas[0].n_source,) + tuple(m.n_target for m in metas),
        input_pops=(0,),
        input_slices=((0, metas[0].n_source),),
        update_order=tuple(range(1, n)),
        pop_alpha=(0.0,) + tuple(m.alpha for m in metas),
        pop_vth=(1.0,) + tuple(m.v_th for m in metas),
        in_edges=((),) + tuple((i,) for i in range(len(metas))),
        proj_src=tuple(range(len(metas))),
        proj_tgt=tuple(range(1, n)),
        proj_back=(False,) * len(metas),
        back_sources=(),
    )


def _layer_params(exe) -> Tuple[jnp.ndarray, ...]:
    """The operand arrays of one lowered layer (a pytree leaf tuple); a
    serial layer's are host arrays, put on the device by the form that
    runs them."""
    if isinstance(exe, SerialExecutable):
        return (exe.row_weight, exe.row_delay, exe.row_src, exe.row_tgt)
    return (exe.wdm_stack, exe.col_source, exe.col_delay)


#: Counters the launch program computes when a projection runs the
#: spike-driven form, in the order of its counter array's rows.
LAUNCH_COUNTERS = ("events.routed", "spikes.emitted", "route.passes")
#: Each counter is carried as two int32 digits, ``lo + hi * 2**24``, so a
#: launch may count past 2**31.
_DIGIT = 24


def _count_add(acc: jnp.ndarray, step: jnp.ndarray) -> jnp.ndarray:
    """``acc`` (n, 2) digits plus this step's (n,) int32 counts."""
    lo = acc[:, 0] + step
    return jnp.stack([lo & ((1 << _DIGIT) - 1), acc[:, 1] + (lo >> _DIGIT)],
                     axis=1)


def read_counters(counts) -> dict:
    """Host totals of a launch's counter digits (any leading axes, such as
    the vmapped path's lanes)."""
    a = np.asarray(counts).astype(np.int64).reshape(-1, len(LAUNCH_COUNTERS), 2)
    tot = (a[:, :, 0] + (a[:, :, 1] << _DIGIT)).sum(axis=0)
    return {name: int(v) for name, v in zip(LAUNCH_COUNTERS, tot)}


def _iaf_to_rows(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    """(B, n) -> the (rows, 128) lane layout of :func:`iaf_layout`."""
    flat = x.reshape(-1)
    return jnp.pad(flat, (0, rows * 128 - flat.size)).reshape(rows, 128)


def _init_graph_carry(
    plan: GraphPlan,
    metas: Tuple[LayerMeta, ...],
    forms: Tuple[str, ...],
    batch: int,
    neuron_params: Tuple[Tuple[jnp.ndarray, ...], ...] = (),
):
    """Fresh scan state: per-projection rings, per-population neuron
    state, and the back-edge feedback ring.  Called only while a jit
    entry is traced, so the state is made inside the launch program; the
    ``carry.traced`` counter records each such trace.

    An Eq. 1 population starts from zero ``(v, z)``; an ``iaf_psc_exp``
    population from its initial potentials (``neuron_params``, one
    ``(v0,)`` per such population in update order, tiled over the batch)
    with zero current and no refractory count, its ``(v, i, r)`` in the
    kernel's lane layout and no ``z``.  A spike-driven projection's ring
    is int32."""
    tracing.count("carry.traced")
    proj = []
    for meta, form in zip(metas, forms):
        if meta.paradigm == "serial":
            proj.append(
                jnp.zeros(
                    (meta.delay_range + 1, batch, meta.n_target),
                    jnp.int32 if form == "spike" else jnp.float32,
                )
            )
        else:
            proj.append(
                jnp.zeros((meta.ring_depth, meta.n_source, batch), jnp.int8)
            )
    pop_v = []
    v0s = iter(neuron_params)
    for p in plan.update_order:
        n = plan.pop_sizes[p]
        if plan.iaf(p) is None:
            pop_v.append(jnp.zeros((batch, n), jnp.float32))
            continue
        rows = iaf_layout(batch, n)
        (v0,) = next(v0s)
        v = _iaf_to_rows(jnp.broadcast_to(v0, (batch, n)), rows)
        zero = jnp.zeros((rows, 128), jnp.int32)
        pop_v.append((v, zero, zero))
    # spike state crossing timesteps is int8 (spikes are exactly 0/1, the
    # f32<->int8 casts are bit-exact) — same layout as the parallel spike
    # history rings, 4x less carried-state traffic
    pop_z = [
        jnp.zeros((batch, plan.pop_sizes[p]), jnp.int8)
        if plan.iaf(p) is None else ()
        for p in plan.update_order
    ]
    feedback = tuple(
        jnp.zeros((batch, plan.pop_sizes[s]), jnp.int8)
        for s in plan.back_sources
    )
    return (tuple(proj), tuple(pop_v), tuple(pop_z), feedback)


def _read_bytes(arrays) -> int:
    """Bytes a host read of ``arrays`` moves, counted only for a sink."""
    return sum(a.nbytes for a in arrays) if tracing.active() else 0


def _scan_network(
    plan: GraphPlan,
    metas: Tuple[LayerMeta, ...],
    forms: Tuple[str, ...],       # per proj: "event" | "sparse" | "dense" | "spike" | "-"
    interpret: bool | None,
    params: List[Tuple[jnp.ndarray, ...]],
    spikes: jnp.ndarray,          # (T, B, n_input) f32
    valid_steps: jnp.ndarray | None = None,   # (B,) i32 true length per request
):
    """One scan over the train: ``(outs, counts)``, one train per
    non-input population in update order, and the launch's counter
    digits (:data:`LAUNCH_COUNTERS`) where a projection runs the
    spike-driven form, else ``()``.  ``params`` holds each projection's
    operands, then one ``(v0,)`` per ``iaf_psc_exp`` population."""
    # Step-count mask: batch slot b is live while t < valid_steps[b].  The
    # mask is applied entirely OUTSIDE the scan (one vectorized multiply on
    # the input train and one per population's stacked output) so masking
    # costs nothing per timestep.  Padded timesteps are provably inert per
    # request: the input mask stops them injecting external spikes, the
    # output mask forces their emitted spikes to exact zeros, and because
    # the scan is causal and batch slots are independent, the first
    # valid_steps[b] outputs are bit-identical to running that request
    # alone (live entries are multiplied by 1.0 — bit-exact).
    live = None
    if valid_steps is not None:
        live = (
            jnp.arange(spikes.shape[0], dtype=jnp.int32)[:, None]
            < valid_steps[None, :]
        ).astype(spikes.dtype)[:, :, None]               # (T, B, 1)
        spikes = spikes * live

    vz_slot = {p: k for k, p in enumerate(plan.update_order)}
    fb_slot = {s: k for k, s in enumerate(plan.back_sources)}
    counting = "spike" in forms
    batch = spikes.shape[1]

    def step(carry, x_t):
        t, proj_states, pop_v, pop_z, feedback = carry[:5]
        pop_out = [None] * len(plan.pop_sizes)
        for p, (a, b) in zip(plan.input_pops, plan.input_slices):
            pop_out[p] = x_t if (a, b) == (0, x_t.shape[1]) else x_t[:, a:b]
        new_proj = list(proj_states)
        new_v, new_z = list(pop_v), list(pop_z)
        step_counts = [jnp.int32(0)] * len(LAUNCH_COUNTERS)
        for p in plan.update_order:
            k = vz_slot[p]
            iaf = plan.iaf(p)
            i_nb = None               # summed current, (n_target, B)
            for ei in plan.in_edges[p]:
                meta, form = metas[ei], forms[ei]
                # back-edges read the source's spikes from the previous
                # timestep (feedback ring, carried int8 — the f32 cast of
                # 0/1 spikes is exact); forward edges cascade within the
                # step in topological order
                x = (
                    feedback[fb_slot[plan.proj_src[ei]]].astype(jnp.float32)
                    if plan.proj_back[ei]
                    else pop_out[plan.proj_src[ei]]
                )
                if form == "spike":
                    ring, i_bt, (events, passes) = serial_project_spike(
                        *params[ei], proj_states[ei], x, t,
                        n_target=meta.n_target, layout=meta.spike,
                    )
                    new_proj[ei] = ring
                    step_counts[0] += events
                    step_counts[2] += jnp.maximum(passes - 1, 0)
                    i_e = i_bt if iaf is not None else i_bt.T.astype(
                        jnp.float32)
                elif meta.paradigm == "serial":
                    proj_fn = {
                        "dense": serial_project_dense,
                        "sparse": serial_project_sparse,
                    }.get(form, serial_project)
                    ring, i_bt = proj_fn(
                        *params[ei], proj_states[ei], x, t,
                        delay_range=meta.delay_range,
                        n_target=meta.n_target, interpret=interpret,
                    )
                    new_proj[ei] = ring
                    i_e = i_bt.T
                else:
                    hist, i_e = parallel_project(
                        *params[ei], proj_states[ei], x, t,
                        interpret=interpret, write_first=meta.zero_delay,
                    )
                    new_proj[ei] = hist
                if iaf is not None and i_e.dtype != jnp.int32:
                    # integer sums of integer weights: exact in f32
                    i_e = i_e.T.astype(jnp.int32)
                i_nb = i_e if i_nb is None else i_nb + i_e
            if iaf is not None:
                n = plan.pop_sizes[p]
                rows = iaf_layout(batch, n)
                v, i_syn, r = pop_v[k]
                v, i_syn, r, z = iaf_psc_exp(
                    _iaf_to_rows(i_nb, rows), v, i_syn, r, consts=iaf,
                    interpret=interpret,
                )
                new_v[k] = (v, i_syn, r)
                z_bn = z.reshape(-1)[: batch * n].reshape(batch, n)
                pop_out[p] = z_bn.astype(jnp.float32)
                if counting:
                    step_counts[1] += z_bn.sum()
                continue
            v_new, z_new = lif_update(
                i_nb, pop_v[k].T, pop_z[k].T.astype(jnp.float32),
                alpha=plan.pop_alpha[p], v_th=plan.pop_vth[p],
                interpret=interpret,
            )
            # previous-spike state crosses the timestep as int8 (exact:
            # spikes are 0/1); the f32 train is what the step emits and
            # what same-step forward projections consume
            new_v[k], new_z[k] = v_new.T, z_new.T.astype(jnp.int8)
            pop_out[p] = z_new.T
            if counting:
                step_counts[1] += z_new.sum().astype(jnp.int32)
        new_feedback = tuple(
            pop_out[s].astype(jnp.int8) for s in plan.back_sources
        )
        # emit ONE train per (non-input) population — a fan-in target is
        # stacked once however many projections converge on it; the
        # launch wrappers expand to the per-projection API view outside
        # the scan (aliased, no extra device buffers)
        outs = tuple(pop_out[p] for p in plan.update_order)
        new = (
            t + 1, tuple(new_proj), tuple(new_v), tuple(new_z), new_feedback
        )
        if counting:
            new += (_count_add(carry[5], jnp.stack(step_counts)),)
        return new, outs

    n_proj = len(metas)
    init = (jnp.int32(0),) + _init_graph_carry(
        plan, metas, forms, batch, tuple(params[n_proj:])
    )
    if counting:
        init += (jnp.zeros((len(LAUNCH_COUNTERS), 2), jnp.int32),)
    final, outs = jax.lax.scan(step, init, spikes)
    if live is not None:
        outs = tuple(z * live for z in outs)
    return outs, (final[5] if counting else ())


def _batched_scan(
    plan: GraphPlan,
    metas: Tuple[LayerMeta, ...],
    forms: Tuple[str, ...],
    interpret: bool | None,
    params: List[Tuple[jnp.ndarray, ...]],
    spikes: jnp.ndarray,          # (T, B, n_input) f32
    valid_steps: jnp.ndarray | None = None,   # (B,) i32
):
    """``jax.vmap`` of the single-request scan over the request axis.

    Each request runs its own width-1 scan, from the width-1 zero state
    that scan builds inside its lane; vmap batches them.  The per-lane
    ``valid_steps`` mask is preserved — lanes with 0 valid steps (padded
    slots) emit exact zeros just like the fused path.  Counter digits
    come back per lane.
    """

    def one(sp, vs):              # sp (T, n_in), vs () i32 or None
        outs, counts = _scan_network(
            plan, metas, forms, interpret, params, sp[:, None, :],
            None if vs is None else vs[None],
        )
        return tuple(z[:, 0] for z in outs), counts

    if valid_steps is None:
        return jax.vmap(lambda sp: one(sp, None), in_axes=1,
                        out_axes=(1, 0))(spikes)
    return jax.vmap(one, in_axes=(1, 0), out_axes=(1, 0))(spikes, valid_steps)


@dataclasses.dataclass(frozen=True)
class TemporalPlan:
    """The graph plan's temporal-parallel decomposition.

    ``update_order`` splits into three contiguous topological intervals:
    ``pre`` and ``post`` populations have no back-edge coupling and run
    whole-train (all T steps at once, carry semantics resolved by the
    associative scan); the ``block`` interval — from the earliest
    back-edge target to the latest back-edge source — keeps its
    step-serial rings and runs through the ordinary fused scan on
    ``sub_plan``, reading the already-computed ``ext_sources`` trains as
    its external input.  A pure feed-forward graph has an empty block
    and runs entirely whole-train.
    """

    pre: Tuple[int, ...]
    block: Tuple[int, ...]
    post: Tuple[int, ...]
    ext_sources: Tuple[int, ...]      # pops whose trains feed the block
    sub_plan: GraphPlan | None        # fused-scan plan of the block
    modes: dict                       # temporal pop -> reset-resolution mode


def _temporal_split(plan: GraphPlan):
    """Split ``update_order`` into (pre, block, post) around back-edges."""
    order = plan.update_order
    backs = [i for i, b in enumerate(plan.proj_back) if b]
    if not backs:
        return order, (), ()
    pos = {p: k for k, p in enumerate(order)}
    lo = min(pos[plan.proj_tgt[i]] for i in backs)
    # a back-edge source outside update_order (an input population) never
    # extends the block: its train is external, not produced by the scan
    hi = max(pos.get(plan.proj_src[i], -1) for i in backs)
    hi = max(hi, lo)
    return order[:lo], order[lo : hi + 1], order[hi + 1 :]


def _temporal_subplan(plan: GraphPlan, block: Tuple[int, ...]):
    """The block's fused-scan plan: same populations/projections, but the
    update order is the block interval and every out-of-block source pop
    (original inputs and whole-train pre populations alike) becomes an
    input population reading a column range of the augmented train."""
    bset = frozenset(block)
    ext = sorted(
        {
            plan.proj_src[ei]
            for p in block
            for ei in plan.in_edges[p]
            if plan.proj_src[ei] not in bset
        }
    )
    slices, off = [], 0
    for s in ext:
        w = plan.pop_sizes[s]
        slices.append((off, off + w))
        off += w
    sub = GraphPlan(
        pop_sizes=plan.pop_sizes,
        input_pops=tuple(ext),
        input_slices=tuple(slices),
        update_order=tuple(block),
        pop_alpha=plan.pop_alpha,
        pop_vth=plan.pop_vth,
        in_edges=plan.in_edges,
        proj_src=plan.proj_src,
        proj_tgt=plan.proj_tgt,
        proj_back=plan.proj_back,
        back_sources=plan.back_sources,
        pop_iaf=plan.pop_iaf,
    )
    return tuple(ext), sub


def _temporal_network(
    plan: GraphPlan,
    metas: Tuple[LayerMeta, ...],
    forms: Tuple[str, ...],      # per proj: serial forms + "temporal[_sparse]"
    interpret: bool | None,
    tplan: TemporalPlan,
    max_iters: int,
    params: List[Tuple[jnp.ndarray, ...]],
    spikes: jnp.ndarray,         # (T, B, n_input) f32
    valid_steps: jnp.ndarray | None = None,
):
    """Whole-train executor: no scan over feed-forward segments.

    Masking follows the fused path's contract exactly — the input train
    is masked once up front, intermediate trains run unmasked (padded
    steps of a causal network can only influence padded outputs), and
    the per-population outputs are masked once at the end — so the live
    prefix is bit-identical to a solo run and padded steps emit exact
    zeros.
    """
    live = None
    if valid_steps is not None:
        live = (
            jnp.arange(spikes.shape[0], dtype=jnp.int32)[:, None]
            < valid_steps[None, :]
        ).astype(spikes.dtype)[:, :, None]               # (T, B, 1)
        spikes = spikes * live

    pop_out = [None] * len(plan.pop_sizes)
    for p, (a, b) in zip(plan.input_pops, plan.input_slices):
        pop_out[p] = (
            spikes if (a, b) == (0, spikes.shape[2]) else spikes[:, :, a:b]
        )
    aux = {}

    def whole_train(p):
        i_full = None                                    # (T, B, n) current
        for ei in plan.in_edges[p]:
            meta = metas[ei]
            x = pop_out[plan.proj_src[ei]]
            if forms[ei] == "temporal_sparse":
                i_e = temporal_project_sparse(
                    *params[ei], x, delay_range=meta.delay_range,
                    n_target=meta.n_target,
                )
            else:
                i_e = temporal_project_dense(params[ei][0], x)
            i_full = i_e if i_full is None else i_full + i_e
        z, iters, residual = temporal_lif(
            i_full, alpha=plan.pop_alpha[p], v_th=plan.pop_vth[p],
            mode=tplan.modes[p], max_iters=max_iters, interpret=interpret,
        )
        pop_out[p] = z
        aux[p] = (iters, residual)

    for p in tplan.pre:
        whole_train(p)
    if tplan.block:
        aug = [pop_out[s] for s in tplan.ext_sources]
        aug = aug[0] if len(aug) == 1 else jnp.concatenate(aug, axis=2)
        block_outs, _ = _scan_network(
            tplan.sub_plan, metas, forms, interpret, params, aug, None,
        )
        for p, z in zip(tplan.block, block_outs):
            pop_out[p] = z
    for p in tplan.post:
        whole_train(p)

    outs = tuple(pop_out[p] for p in plan.update_order)
    if live is not None:
        outs = tuple(z * live for z in outs)
    # per-pop reset-resolution telemetry, update_order aligned; (0, 0)
    # marks a step-serial block population (no fixed point ran)
    zero = jnp.int32(0)
    aux_iters = jnp.stack(
        [aux.get(p, (zero, zero))[0] for p in plan.update_order]
    )
    aux_resid = jnp.stack(
        [aux.get(p, (zero, zero))[1] for p in plan.update_order]
    )
    return outs, (aux_iters, aux_resid)


def _param_axes(meta: LayerMeta, form: str) -> Tuple[Tuple, ...]:
    """Logical-axis names per operand array (for ``snn_rules`` placement)."""
    if meta.paradigm == "serial":
        if form == "dense":
            return ((None, None, "neurons"),)      # (d_slots, S, T)
        if form == "sparse":
            # ELL rows are (delay_slot, target) pairs — the target-neuron
            # axis in disguise
            return (("neurons", None), ("neurons", None))  # ell_val, ell_idx
        return (("rows",),) * 4                    # weight/delay/src/tgt
    # parallel: wdm_stack (n_target, C), col_source (C,), col_delay (C,)
    return (("neurons", "cols"), ("cols",), ("cols",))


class NetworkExecutable:
    """A whole compiled application graph, lowered once, run in one scan."""

    def __init__(
        self,
        metas: Tuple[LayerMeta, ...],
        params: List[Tuple[jnp.ndarray, ...]],
        name: str = "snn",
        *,
        plan: GraphPlan | None = None,
        report: CompileReport | None = None,
        cost_model: SerialBatchCostModel | None = None,
    ):
        self.metas = tuple(metas)
        self.params = list(params)
        self.name = name
        #: The application-graph execution plan; a plain chain when the
        #: handle was constructed from bare metas.
        self.plan = plan or (_chain_plan(self.metas) if self.metas else None)
        #: Serving-layer routing tag: the registered model name this
        #: handle serves (set by ``network_executable(..., model=...)``).
        self.model: str | None = None
        #: The report this executable was built from; launch paths record
        #: their serial kernel-form decisions into ``report.serial_forms``.
        self.report = report
        #: Crossover model deciding event vs dense serial form per batch.
        self.cost_model = cost_model or DEFAULT_SERIAL_BATCH_COST
        self._fns = {}       # (path, interpret, forms, max_iters) -> jit fn
        self._dense = {}     # layer index -> (d_slots, S, T) dense operand
        self._sparse = {}    # layer index -> (ell_val, ell_idx) ELL operands
        self._temporal = {}  # layer index -> whole-train dense operand
        self._event = {}     # layer index -> event-form device operands
        self._spike = {}     # layer index -> (row_len, rows) spike operands
        #: One ``(v0,)`` device operand per ``iaf_psc_exp`` population, in
        #: update order (set by :meth:`build`).
        self.neuron_params: Tuple[Tuple[jnp.ndarray, ...], ...] = ()
        #: Per launch whose counters were read (``run``): the
        #: :data:`LAUNCH_COUNTERS` totals, in launch order.  Only a network
        #: with a spike-driven projection counts.
        self.launch_counts: List[dict] = []
        self._counts = None  # the last launch's counter digits, unread
        self._nonneg = {}    # layer index -> all weights >= 0? (mode pick)
        self._tplan = None   # cached TemporalPlan (topology, not placement)
        self._mesh = None    # set by shard(); None = identity fallback
        self._rules = None
        #: Device scalar from the last launch: True iff every output
        #: entry was exactly 0.0 or 1.0 (NaN/Inf equal neither).  The
        #: check runs *inside* the jitted launch program — fused with the
        #: scan epilogue it costs no extra dispatch and reads the trains
        #: while they are still hot on the compute threads — so the
        #: serving supervisor can validate fault-free launches without a
        #: host-side pass over the data.
        self.last_check = None

    def jit_entries(self) -> int:
        """Distinct jitted scan entries held by this handle."""
        return len(self._fns)

    @classmethod
    def build(cls, net: SNNNetwork, report: CompileReport) -> "NetworkExecutable":
        if len(report.layers) != len(net.layers):
            raise ValueError("report does not match network")
        plan = _graph_plan(net)
        metas, params = [], []
        for i, (layer, compiled) in enumerate(
            zip(net.layers, report.layers)
        ):
            exe = get_layer_executable(compiled, layer.lif)
            tgt = plan.proj_tgt[i]
            serial = isinstance(exe, SerialExecutable)
            metas.append(
                LayerMeta(
                    paradigm=compiled.paradigm,
                    n_source=exe.n_source,
                    n_target=exe.n_target,
                    delay_range=exe.delay_range,
                    alpha=plan.pop_alpha[tgt],
                    v_th=plan.pop_vth[tgt],
                    n_rows=int(
                        exe.row_weight.shape[0]
                        if serial
                        else exe.col_source.shape[0]
                    ),
                    zero_delay=False if serial else exe.zero_delay,
                    spike=spike_layout(exe) if serial else None,
                )
            )
            params.append(_layer_params(exe))
        handle = cls(
            tuple(metas), params, name=getattr(net, "name", "snn"),
            plan=plan, report=report,
        )
        handle.neuron_params = tuple(
            (jnp.asarray(net.population_lif(p).v0_fixed(plan.pop_sizes[p])),)
            for p in plan.update_order if plan.iaf(p) is not None
        )
        return handle

    @property
    def n_input(self) -> int:
        """Width of the external spike train (summed input pop sizes)."""
        return sum(b - a for a, b in self.plan.input_slices)

    # -- serial kernel-form selection ----------------------------------------
    def serial_forms(
        self, batch: int, serial_form: str = "auto"
    ) -> Tuple[str, ...]:
        """Per-projection kernel form at this batch: "event" | "sparse" |
        "dense" | "spike" ("-" = parallel).

        ``serial_form`` forces every serial projection onto one form
        ("event" / "sparse" / "dense" / "spike"); "auto" asks the cost
        model's argmin per projection
        (:meth:`~repro.core.cost_model.SerialBatchCostModel.choose_form`),
        in which the spike-driven form competes wherever the projection's
        synapses pack into its words (``LayerMeta.spike``).  Forcing
        "dense" on a projection over the cost model's element cap raises —
        the dense operand physically shouldn't exist — as does forcing
        "spike" where the synapses do not pack; every form is
        bit-identical on outputs, so the choice only moves throughput.
        """
        if serial_form not in ("auto", "event", "sparse", "dense", "spike"):
            raise ValueError(f"unknown serial_form {serial_form!r}")
        forms = []
        for meta in self.metas:
            if meta.paradigm != "serial":
                forms.append("-")
            elif serial_form == "spike" and meta.spike is None:
                raise ValueError(
                    "serial_form='spike' forced on a projection whose "
                    "synapses do not pack into the spike-driven form's "
                    "32-bit words"
                )
            elif serial_form != "auto":
                if serial_form == "dense" and not self.cost_model.dense_fits(
                    meta.n_source, meta.n_target, meta.delay_range
                ):
                    raise ValueError(
                        f"serial_form='dense' forced on a projection whose "
                        f"({meta.delay_range + 1}, {meta.n_source}, "
                        f"{meta.n_target}) dense operand exceeds the "
                        f"{self.cost_model.dense_element_cap}-element cap — "
                        f"use serial_form='sparse' (or 'auto')"
                    )
                forms.append(serial_form)
            else:
                forms.append(
                    self.cost_model.choose_form(
                        meta.n_rows, meta.n_source, meta.n_target,
                        meta.delay_range, batch,
                        spike_ok=meta.spike is not None,
                    )
                )
        return tuple(forms)

    def _serial_exe(self, i: int) -> SerialExecutable:
        meta, p = self.metas[i], self.params[i]
        return SerialExecutable(
            n_source=meta.n_source, n_target=meta.n_target,
            delay_range=meta.delay_range,
            row_weight=p[0], row_delay=p[1], row_src=p[2], row_tgt=p[3],
            lif=LIFParams(alpha=meta.alpha, v_th=meta.v_th),
        )

    def _event_param(self, i: int) -> Tuple[jnp.ndarray, ...]:
        """The layer's event-form operands on the device, cached."""
        ops = self._event.get(i)
        if ops is None:
            axes = _param_axes(self.metas[i], "event")
            ops = tuple(
                self._place(jnp.asarray(a), ax)
                for a, ax in zip(self.params[i], axes)
            )
            self._event[i] = ops
        return ops

    def _spike_param(self, i: int) -> Tuple[jnp.ndarray, ...]:
        """The layer's spike-driven operands ``(row_len, rows)``, built
        once and cached."""
        ops = self._spike.get(i)
        if ops is None:
            meta = self.metas[i]
            with tracing.span("lower.spike", rows=meta.n_rows,
                              sources=meta.n_source):
                row_len, rows = spike_serial_operands(
                    self._serial_exe(i), meta.spike
                )
                ops = (jnp.asarray(row_len), jnp.asarray(rows))
            self._spike[i] = ops
        return ops

    def _dense_param(self, i: int) -> Tuple[jnp.ndarray, ...]:
        """The layer's dense-form operand, built once and cached."""
        w = self._dense.get(i)
        if w is None:
            meta = self.metas[i]
            w = jnp.asarray(dense_serial_weights(self._serial_exe(i)))
            w = self._place(w, _param_axes(meta, "dense")[0])
            self._dense[i] = w
        return (w,)

    def _sparse_param(self, i: int) -> Tuple[jnp.ndarray, ...]:
        """The layer's ELL (sparse-form) operands, built once and cached."""
        ell = self._sparse.get(i)
        if ell is None:
            meta = self.metas[i]
            val, idx = sparse_serial_operands(self._serial_exe(i))
            axes = _param_axes(meta, "sparse")
            ell = (
                self._place(jnp.asarray(val), axes[0]),
                self._place(jnp.asarray(idx), axes[1]),
            )
            self._sparse[i] = ell
        return ell

    # -- temporal-parallel structure and forms -------------------------------
    def _weights_nonneg(self, i: int) -> bool:
        v = self._nonneg.get(i)
        if v is None:
            w = np.asarray(self.params[i][0])   # row_weight | wdm_stack
            v = bool(w.size == 0 or w.min() >= 0)
            self._nonneg[i] = v
        return v

    def _temporal_structure(self) -> TemporalPlan:
        """The (cached) temporal decomposition of this graph plan."""
        tp = self._tplan
        if tp is None:
            if self.plan.pop_iaf:
                raise ValueError(
                    "the temporal path's closed forms are those of Eq. 1; "
                    "this network has iaf_psc_exp populations — run it "
                    "through the step scan (temporal=False)"
                )
            pre, block, post = _temporal_split(self.plan)
            if block:
                ext, sub = _temporal_subplan(self.plan, block)
            else:
                ext, sub = (), None
            modes = {}
            for p in pre + post:
                nonneg = all(
                    self._weights_nonneg(ei)
                    for ei in self.plan.in_edges[p]
                )
                modes[p] = choose_temporal_mode(
                    self.plan.pop_alpha[p], self.plan.pop_vth[p],
                    nonneg_weights=nonneg,
                )
            tp = TemporalPlan(
                pre=pre, block=block, post=post, ext_sources=ext,
                sub_plan=sub, modes=modes,
            )
            self._tplan = tp
        return tp

    def temporal_forms(
        self, batch: int, steps: int, serial_form: str = "auto"
    ) -> Tuple[str, ...]:
        """Per-projection form for the temporal launch path.

        Projections targeting the step-serial block keep their ordinary
        serial form (same three-way choice as :meth:`serial_forms`);
        projections targeting whole-train populations run ``"temporal"``
        (one dense whole-train contraction) or ``"temporal_sparse"``
        (the ELL gather vmapped over time), picked by the cost model's
        operand comparison — or forced to the matching operand by
        ``serial_form``.  Like every form, the choice never changes
        outputs.
        """
        tp = self._temporal_structure()
        bset = frozenset(tp.block)
        base = self.serial_forms(batch, serial_form)
        forms = []
        for i, meta in enumerate(self.metas):
            if self.plan.proj_tgt[i] in bset:
                forms.append(base[i])
                continue
            if meta.paradigm == "parallel":
                if not self.cost_model.dense_fits(
                    meta.n_source, meta.n_target, meta.delay_range
                ):  # pragma: no cover - parallel compile densifies under cap
                    raise ValueError(
                        "parallel projection too large for the whole-train "
                        "dense operand; run a non-temporal path"
                    )
                forms.append("temporal")
                continue
            dense_ok = self.cost_model.dense_fits(
                meta.n_source, meta.n_target, meta.delay_range
            )
            if serial_form == "sparse" or not dense_ok:
                forms.append("temporal_sparse")
            elif serial_form == "dense":
                forms.append("temporal")
            else:
                operand = self.cost_model.temporal_operand(
                    meta.n_rows, meta.n_source, meta.n_target,
                    meta.delay_range, batch,
                )
                forms.append(
                    "temporal" if operand == "dense" else "temporal_sparse"
                )
        return tuple(forms)

    def _temporal_param(self, i: int) -> Tuple[jnp.ndarray, ...]:
        """The whole-train dense operand: the serial dense (d_slots, S, T)
        weights verbatim, or the parallel WDM stack scattered back into
        the same delay-stacked layout (integer accumulation — exact)."""
        meta = self.metas[i]
        if meta.paradigm == "serial":
            return self._dense_param(i)
        w = self._temporal.get(i)
        if w is None:
            wdm, col_src, col_dly = (np.asarray(a) for a in self.params[i])
            w_np = np.zeros(
                (meta.delay_range + 1, meta.n_source, meta.n_target),
                np.float32,
            )
            np.add.at(w_np, (col_dly, col_src), wdm.T.astype(np.float32))
            w = self._place(jnp.asarray(w_np), (None, None, "neurons"))
            self._temporal[i] = w
        return (w,)

    def _params_for(self, forms: Tuple[str, ...]) -> List[Tuple]:
        """Each projection's operands for its form, then the neuron
        operands of the ``iaf_psc_exp`` populations."""
        per_form = {
            "event": self._event_param,
            "dense": self._dense_param,
            "sparse": self._sparse_param,
            "spike": self._spike_param,
            "temporal": self._temporal_param,
            "temporal_sparse": self._sparse_param,
        }
        return [
            per_form[form](i) if form in per_form else p
            for i, (form, p) in enumerate(zip(forms, self.params))
        ] + list(self.neuron_params)

    def _record_forms(
        self, path: str, batch: int, forms: Tuple[str, ...]
    ) -> None:
        if self.report is not None:
            self.report.serial_forms[(path, batch)] = forms

    # -- sharding ------------------------------------------------------------
    @property
    def mesh(self):
        """The mesh params are placed on (None = single-device identity)."""
        return self._mesh

    def shard(
        self,
        mesh=None,
        rules: dict | None = None,
        *,
        assignment=None,
    ) -> "NetworkExecutable":
        """Place the lowered operands by the SNN logical-axis rules.

        Routes every projection's weight/delay operands through
        :func:`repro.distributed.sharding.snn_rules` (neurons -> model,
        rows -> model; the launch paths place the request batch on the
        data axis).  With one visible device (:func:`snn_mesh` returns
        ``None``) this is the **identity fallback**: no placement happens
        and outputs are unchanged — CPU CI exercises the same call.
        Returns ``self`` for chaining.

        ``assignment`` switches to **placement-driven** sharding: a
        :class:`repro.placement.DeviceAssignment` (from
        ``build_device_assignment`` on a placed, tiled network) pins each
        projection's operands to the device its target tile landed on,
        replacing the blanket logical-axis rules.  The assignment is
        recorded in ``report.placement``; on one device the put is the
        identity, so the path runs end-to-end on CPU CI.
        """
        if assignment is not None:
            if len(assignment.proj_device) != len(self.metas):
                raise ValueError(
                    f"assignment covers {len(assignment.proj_device)} "
                    f"projections; executable has {len(self.metas)}"
                )
            self.params = [
                tuple(shardlib.placement_put(arr, dev) for arr in p)
                for dev, p in zip(assignment.proj_device, self.params)
            ]
            self._mesh = None      # device pinning replaces mesh placement
            self._rules = None
            self._dense.clear()
            self._sparse.clear()
            self._temporal.clear()
            self._event.clear()
            self._spike.clear()
            self._fns.clear()
            if self.report is not None:
                self.report.placement = assignment
            return self
        mesh = shardlib.snn_mesh() if mesh is None else mesh
        self._rules = rules or shardlib.snn_rules()
        self._mesh = mesh
        if mesh is None:
            return self
        from jax.sharding import NamedSharding

        def place(arr, axes):
            spec = shardlib.spec_for_shape(axes, self._rules, arr.shape, mesh)
            return jax.device_put(arr, NamedSharding(mesh, spec))

        self.params = [
            tuple(
                place(arr, ax)
                for arr, ax in zip(p, _param_axes(meta, "event"))
            )
            for meta, p in zip(self.metas, self.params)
        ]
        # dense/sparse/temporal operands and jitted entries were traced/
        # placed against the old layout; rebuild all lazily
        self._dense.clear()
        self._sparse.clear()
        self._temporal.clear()
        self._event.clear()
        self._spike.clear()
        self._fns.clear()
        return self

    def _place(self, arr, axes):
        if self._mesh is None:
            return arr
        from jax.sharding import NamedSharding

        spec = shardlib.spec_for_shape(axes, self._rules, arr.shape, self._mesh)
        return jax.device_put(arr, NamedSharding(self._mesh, spec))

    def _place_inputs(self, spikes, valid_steps):
        """Put the request batch on the data axis (no-op unsharded)."""
        if self._mesh is None:
            return spikes, valid_steps
        spikes = self._place(spikes, ("steps", "batch", None))
        if valid_steps is not None:
            valid_steps = self._place(valid_steps, ("batch",))
        return spikes, valid_steps

    # -- launch paths --------------------------------------------------------
    def _check_shapes(self, spikes, valid_steps):
        if spikes.ndim != 3 or spikes.shape[2] != self.n_input:
            raise ValueError(
                f"spikes must be (T, B, {self.n_input}); got {spikes.shape}"
            )
        if valid_steps is not None:
            valid_steps = jnp.asarray(valid_steps, jnp.int32)
            if valid_steps.shape != (spikes.shape[1],):
                raise ValueError(
                    f"valid_steps must be ({spikes.shape[1]},); "
                    f"got {valid_steps.shape}"
                )
        return valid_steps

    def _get_fn(
        self, path: str, interpret, forms: Tuple[str, ...],
        max_iters: int | None = None,
    ):
        key = (path, interpret, forms, max_iters)
        fn = self._fns.get(key)
        if fn is None:
            if path == "temporal":
                inner = partial(
                    _temporal_network, self.plan, self.metas, forms,
                    interpret, self._temporal_structure(), max_iters,
                )
            else:
                scan = _batched_scan if path == "vmap" else _scan_network
                inner = partial(
                    scan, self.plan, self.metas, forms, interpret
                )

            def checked(params, spikes, valid_steps):
                # + the fixed point's pass counts (temporal), or the
                # launch's counter digits (a spike-driven projection)
                outs, aux = inner(params, spikes, valid_steps)
                if path != "temporal":
                    aux = (aux,) if len(aux) else ()
                # in-graph output self-check: every spike entry must be
                # exactly 0.0 or 1.0 (subsumes finiteness — NaN and Inf
                # equal neither), reduced to one scalar the launch
                # returns alongside the trains
                ok = jnp.bool_(True)
                for z in outs:
                    ok = jnp.logical_and(
                        ok, jnp.all((z == 0.0) | (z == 1.0))
                    )
                return outs, aux, ok

            fn = jax.jit(checked)
            self._fns[key] = fn
        return fn

    def _launch(self, path, spikes, valid_steps, interpret, serial_form):
        valid_steps = self._check_shapes(spikes, valid_steps)
        steps, batch = spikes.shape[:2]
        with tracing.span("launch.prepare", path=path, batch=batch,
                          steps=steps):
            forms = self.serial_forms(batch, serial_form)
            self._record_forms(
                "vmap" if path == "vmap" else "fused", batch, forms
            )
            fn = self._get_fn(path, interpret, forms)
            spikes, valid_steps = self._place_inputs(
                jnp.asarray(spikes, jnp.float32), valid_steps
            )
            params = self._params_for(forms)
        with tracing.span("launch.dispatch"):
            outs, aux, self.last_check = fn(params, spikes, valid_steps)
        self._counts = aux[0] if aux else None
        # per-population device trains -> the per-projection API view
        # (entry i = projection i's target population; fan-in entries
        # alias the same array)
        slot = {p: k for k, p in enumerate(self.plan.update_order)}
        return tuple(outs[slot[tgt]] for tgt in self.plan.proj_tgt)

    def lower(
        self,
        steps: int,
        batch: int,
        *,
        path: str = "fused",
        interpret: bool | None = None,
        sharding=None,
    ) -> jax.stages.Lowered:
        """The program a ``path`` launch ("fused" | "vmap") of a
        ``(steps, batch)`` masked train runs, lowered ahead of time.

        It is the jit entry :meth:`run_device` / :meth:`run_batched` use,
        so ``.compile().as_text()`` shows what a launch runs.  Arguments
        are shapes only, placed on ``sharding`` when given: a device of a
        described TPU topology compiles the program for that chip
        without one attached.
        """
        forms = self.serial_forms(batch)
        fn = self._get_fn(path, interpret, forms)

        def shape(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

        return fn.lower(
            jax.tree_util.tree_map(shape, self._params_for(forms)),
            shape(jax.ShapeDtypeStruct((steps, batch, self.n_input), jnp.float32)),
            shape(jax.ShapeDtypeStruct((batch,), jnp.int32)),
        )

    def run_device(
        self,
        spikes: np.ndarray,        # (T, B, n_input) 0/1
        *,
        valid_steps: np.ndarray | None = None,   # (B,) true steps per request
        interpret: bool | None = None,
        serial_form: str = "auto",
    ) -> Tuple[jnp.ndarray, ...]:
        """Per-projection spike trains as device arrays — no host sync.

        Entry ``i`` is the spike train of projection ``i``'s *target
        population* (for a chain: exactly the per-layer outputs of the
        pre-graph executor).  Callers that time this must
        ``jax.block_until_ready`` the result.  With ``valid_steps``,
        batch slot ``b`` is masked after its first ``valid_steps[b]``
        timesteps: the live prefix is bit-identical to an unmasked run
        and every padded timestep emits exact zeros, so padded
        micro-batches are provably inert per request.  ``serial_form``
        forces the serial kernel form ("auto" lets the cost model pick
        per projection); the form never changes outputs, only throughput.
        """
        if not self.metas:
            return ()
        return self._launch(
            "fused", spikes, valid_steps, interpret, serial_form
        )

    def run_batched(
        self,
        spikes: np.ndarray,        # (T, B, n_input) 0/1 — B = request axis
        *,
        valid_steps: np.ndarray | None = None,   # (B,) true steps per request
        interpret: bool | None = None,
        serial_form: str = "auto",
    ) -> Tuple[jnp.ndarray, ...]:
        """The explicit batched path: ``jax.vmap`` over the request axis.

        Same layout and same bits as :meth:`run_device` — each request
        runs as an independent width-1 scan lane, so per-request masking
        and the solo-equivalence guarantee carry over verbatim.  Serving
        uses this path for full micro-batches; the differential harness
        (``tests/test_batch_equivalence.py``) pins it against the fused
        and layerwise paths.
        """
        if not self.metas:
            return ()
        return self._launch(
            "vmap", spikes, valid_steps, interpret, serial_form
        )

    def run_temporal(
        self,
        spikes: np.ndarray,        # (T, B, n_input) 0/1
        *,
        valid_steps: np.ndarray | None = None,   # (B,) true steps per request
        interpret: bool | None = None,
        serial_form: str = "auto",
        max_iters: int | None = None,
    ) -> Tuple[jnp.ndarray, ...]:
        """The temporal-parallel path: whole-train, no scan over time.

        Feed-forward populations compute all T timesteps at once — the
        input train is projected in one contraction and the membrane
        recurrence resolved in log depth
        (:mod:`repro.core.runtime.temporal_runtime`); only the back-edge
        interval of the topological order (empty for feed-forward
        graphs) falls back to the step-serial fused scan.  Same output
        layout, masking contract, and bits as :meth:`run_device` in the
        exact reset modes; iterative populations additionally record
        their fixed-point pass count and residual in
        ``report.temporal[(batch, steps)]`` (residual is 0 unless the
        ``max_iters`` cap — default T+1, which guarantees convergence —
        cut the loop short).
        """
        if not self.metas:
            return ()
        valid_steps = self._check_shapes(spikes, valid_steps)
        steps, batch = int(spikes.shape[0]), int(spikes.shape[1])
        with tracing.span("launch.prepare", path="temporal", batch=batch,
                          steps=steps):
            forms = self.temporal_forms(batch, steps, serial_form)
            self._record_forms("temporal", batch, forms)
            cap = int(max_iters) if max_iters else steps + 1
            fn = self._get_fn("temporal", interpret, forms, max_iters=cap)
            spikes, valid_steps = self._place_inputs(
                jnp.asarray(spikes, jnp.float32), valid_steps
            )
            params = self._params_for(forms)
        with tracing.span("launch.dispatch"):
            outs, aux, self.last_check = fn(params, spikes, valid_steps)
        self._record_temporal(batch, steps, cap, aux)
        slot = {p: k for k, p in enumerate(self.plan.update_order)}
        return tuple(outs[slot[tgt]] for tgt in self.plan.proj_tgt)

    def _record_temporal(self, batch, steps, cap, aux) -> None:
        if self.report is None:
            return
        tp = self._temporal_structure()
        with tracing.span("launch.sync", what="passes", arrays=len(aux),
                          bytes=_read_bytes(aux)):
            iters, resid = (np.asarray(a) for a in aux)
        order = self.plan.update_order
        self.report.temporal[(batch, steps)] = TemporalReport(
            split=(len(tp.pre), len(tp.block), len(tp.post)),
            modes=dict(tp.modes),
            iterations={
                p: int(iters[k]) for k, p in enumerate(order)
                if p in tp.modes
            },
            residual={
                p: int(resid[k]) for k, p in enumerate(order)
                if p in tp.modes
            },
            max_iters=cap,
        )

    def run(
        self,
        spikes: np.ndarray,        # (T, B, n_input) 0/1
        *,
        valid_steps: np.ndarray | None = None,
        interpret: bool | None = None,
        serial_form: str = "auto",
        batched: bool = False,
        temporal: bool = False,
    ) -> List[np.ndarray]:
        """Returns the per-projection spike trains [(T, B, n_l) ...]."""
        if temporal:
            launch = self.run_temporal
        else:
            launch = self.run_batched if batched else self.run_device
        self._counts = None
        outs = launch(
            spikes, valid_steps=valid_steps, interpret=interpret,
            serial_form=serial_form,
        )
        counts, self._counts = self._counts, None
        # single host sync, after the whole network finished on device;
        # the counters (a spike-driven network's) come in the same read
        arrays = list(outs) + ([counts] if counts is not None else [])
        with tracing.span("launch.sync", what="outputs", arrays=len(arrays),
                          bytes=_read_bytes(arrays)):
            host = [np.asarray(a) for a in arrays]
        if counts is not None:
            totals = read_counters(host.pop())
            for name, n in totals.items():
                tracing.count(name, n)
            self.launch_counts.append(totals)
        return host


class OutputValidationError(ValueError):
    """A launch returned spike trains that cannot be served.

    Raised by :func:`validate_spike_outputs` when a result violates the
    output contract (shape, dtype, finiteness, binariness).  The serving
    supervisor treats it as a launch *fault* — the corrupted result is
    discarded and the launch retried — rather than serving garbage.
    """


def validate_spike_outputs(
    outs,
    *,
    steps: int,
    batch: int,
    sizes: Optional[Tuple[int, ...]] = None,
) -> None:
    """Post-launch guard: every output train must be a servable spike train.

    Checks, per projection output: shape ``(steps, batch, n_target)``
    (``sizes`` supplies the expected widths when known), float32 dtype,
    and every entry exactly 0.0 or 1.0.  The binary check subsumes
    finiteness — NaN and Inf compare unequal to both 0 and 1 — so one
    vectorized pass covers the divergent-membrane (non-finite) and
    corrupted-spike (non-binary) failure signatures; the raised message
    still distinguishes them.  Raises :class:`OutputValidationError`;
    returns ``None`` on clean outputs.
    """
    if sizes is not None and len(outs) != len(sizes):
        raise OutputValidationError(
            f"expected {len(sizes)} projection outputs; got {len(outs)}"
        )
    for i, z in enumerate(outs):
        arr = np.asarray(z)
        want = (steps, batch) if sizes is None else (steps, batch, sizes[i])
        if arr.ndim != 3 or arr.shape[: len(want)] != want:
            raise OutputValidationError(
                f"projection {i}: expected (T, B, n_target) shape starting "
                f"{want}; got {arr.shape}"
            )
        if arr.dtype != np.float32:
            raise OutputValidationError(
                f"projection {i}: expected float32 spikes; got {arr.dtype}"
            )
        if not bool(np.all((arr == 0.0) | (arr == 1.0))):
            kind = (
                "non-finite" if not bool(np.all(np.isfinite(arr)))
                else "non-binary"
            )
            raise OutputValidationError(
                f"projection {i}: {kind} entries in the output spike train"
            )


def _matches_network(exe: NetworkExecutable, net: SNNNetwork) -> bool:
    """Does the cached executable still reflect the net's graph and LIF?

    The network contributes the graph plan (topology, population sizes,
    effective LIF parameters) and projection shapes to the executable
    (weights come from the report's programs), so those are the facts
    that can go stale.
    """
    if len(exe.metas) != len(net.layers):
        return False
    try:
        plan = _graph_plan(net)
    except (ValueError, KeyError):
        return False
    if plan != exe.plan:
        return False
    return all(
        meta.n_source == layer.n_source
        and meta.n_target == layer.n_target
        for meta, layer in zip(exe.metas, net.layers)
    )


def network_executable(
    net: SNNNetwork, report: CompileReport, model: str | None = None
) -> NetworkExecutable:
    """The report's cached fused executable, (re)building when stale.

    ``model`` tags the handle with the serving-layer model name it is
    keyed under (multi-model pools route by this name); the tag survives
    rebuilds so diagnostics can attribute re-lowerings to a model.
    """
    exe = report.executable
    if exe is None or not _matches_network(exe, net):
        exe = NetworkExecutable.build(net, report)
        report.executable = exe
    if model is not None:
        exe.model = model
    return exe


def release_network_executable(report: CompileReport) -> int:
    """Drop the report's fused executable and every per-layer lowering.

    The eviction path of the serving pool: frees the host-side handles
    (jit entries, lowered operand arrays) held for a model that fell out
    of the LRU cap.  Returns the number of cache slots cleared.  The next
    ``network_executable`` call on this report re-lowers from the compiled
    programs — visible in ``lowering_counts`` — so eviction cost is never
    hidden.
    """
    cleared = 0
    if report.executable is not None:
        report.executable = None
        cleared += 1
    for compiled in report.layers:
        if compiled.executable is not None:
            compiled.executable = None
            cleared += 1
    return cleared
