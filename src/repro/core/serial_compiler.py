"""Serial (ARM, event-driven) paradigm compiler — paper §III-A.

Mapping pipeline (Fig. 2): application-graph vertex -> equal sub-population
split at the 255-neuron PE capacity -> per-(source-part x target-part) cell,
emit the event-driven data structures:

* master population table — one 96-bit entry per source vertex; a spike's
  source-vertex key unlocks the entry, which points into the address list.
* address list — one 32-bit row per source neuron: (first address, row
  length) of that neuron's block in the synaptic matrix.
* synaptic matrix — one block per source neuron; each 32-bit row packs
  (weight, delay, synapse type, target neuron index) for one synapse.

If a cell's synaptic matrix overflows the 96 kB DTCM (density >~ 25%) the
matrix is split evenly across 2-4 adjacent PEs (paper §IV-A); the other
structures are replicated on each.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import numpy as np

from .cost_model import (
    equal_parts,
    serial_pe_cost,
    serial_pe_overhead,
    total,
)
from .hw import SpiNNaker2Config, DEFAULT_S2
from .layer import LayerCharacter, SNNLayer, is_sparse

# --- 32-bit synaptic row packing -------------------------------------------
# | 31..24 weight magnitude (8b) | 23..17 delay (7b) | 16 type | 15..0 index |
# The delay field holds the delay itself, 0..127 steps: published delays in
# milliseconds at sub-millisecond steps reach tens of steps, and a
# back-edge may carry delay 0.  The index is the target's place in its
# cell (a PE holds at most 255 neurons).
_W_SHIFT, _D_SHIFT, _T_SHIFT = 24, 17, 16
_D_MASK = (1 << 7) - 1
_IDX_MASK = (1 << 16) - 1
#: Largest delay a packed row holds.
MAX_ROW_DELAY = _D_MASK


def pack_rows(weights: np.ndarray, delays: np.ndarray, tgt_idx: np.ndarray) -> np.ndarray:
    if delays.size and (delays.min() < 0 or delays.max() > MAX_ROW_DELAY):
        raise ValueError(f"a synaptic row holds delays 0..{MAX_ROW_DELAY}")
    mag = np.abs(weights).astype(np.uint32) & 0xFF
    dly = delays.astype(np.uint32) & _D_MASK
    typ = (weights < 0).astype(np.uint32)  # 1 = inhibitory
    idx = tgt_idx.astype(np.uint32) & _IDX_MASK
    return (mag << _W_SHIFT) | (dly << _D_SHIFT) | (typ << _T_SHIFT) | idx


def decode_rows(rows: np.ndarray):
    """``(weight, delay, index)`` of packed rows as float32, int32, int32."""
    w = ((rows >> _W_SHIFT) & 0xFF).astype(np.float32)
    np.negative(w, out=w, where=((rows >> _T_SHIFT) & 0x1).astype(bool))
    dly = ((rows >> _D_SHIFT) & _D_MASK).astype(np.int32)
    return w, dly, (rows & _IDX_MASK).astype(np.int32)


def unpack_rows(rows: np.ndarray):
    w, dly, idx = decode_rows(rows)
    return w.astype(np.float64), dly.astype(np.int64), idx.astype(np.int64)


@dataclasses.dataclass
class SerialCell:
    """One (source-part x target-part) machine-graph cell."""

    src_start: int
    src_size: int
    tgt_start: int
    tgt_size: int
    master_population_table: np.ndarray  # (n_source_vertex, 3): key, offset, len
    address_list: np.ndarray             # (src_size, 2): row_start, row_len
    synaptic_rows: np.ndarray            # (n_synapses,) uint32 packed
    matrix_split: int                    # PEs this cell occupies (1..4)
    cost: dict

    @property
    def pe_count(self) -> int:
        return self.matrix_split


@dataclasses.dataclass
class SerialProgram:
    layer_name: str
    n_source: int
    n_target: int
    delay_range: int
    cells: List[SerialCell]

    @property
    def pe_count(self) -> int:
        return sum(c.pe_count for c in self.cells)

    @property
    def dtcm_bytes(self) -> float:
        return float(sum(total(c.cost) for c in self.cells))


def _matrix_split_factor(
    matrix_bytes: float, overhead: float, hw: SpiNNaker2Config
) -> int:
    budget = hw.dtcm_bytes - overhead
    if budget <= 0:
        raise ValueError("serial PE overhead alone exceeds DTCM")
    k = max(1, math.ceil(matrix_bytes / budget))
    return k


def serial_pe_count(
    character: LayerCharacter, *, hw: SpiNNaker2Config = DEFAULT_S2
) -> int:
    """Analytic PE count from the layer character alone (Table I driven)."""
    character.validate()
    src_parts = equal_parts(character.n_source, hw.max_neurons_per_pe)
    tgt_parts = equal_parts(character.n_target, hw.max_neurons_per_pe)
    n_src_vertex = len(src_parts)
    pes = 0
    for sp in src_parts:
        for tp in tgt_parts:
            overhead = serial_pe_overhead(
                tp, sp, character.delay_range, n_src_vertex, hw=hw
            )
            matrix = (32 / 8) * sp * tp * character.weight_density
            k = _matrix_split_factor(matrix, overhead, hw)
            if k > hw.max_matrix_split:
                # Paper caps the matrix split at 4 adjacent PEs; beyond that
                # the target part itself must shrink.  Never triggered on the
                # paper's dataset grid (verified in tests).
                k = hw.max_matrix_split
                sub = serial_pe_count(
                    LayerCharacter(
                        sp, tp, character.weight_density, character.delay_range
                    ),
                    hw=dataclasses.replace(
                        hw, max_neurons_per_pe=max(1, tp // 2)
                    ),
                )
                pes += sub
                continue
            pes += k
    return pes


def serial_pe_count_exact(
    layer: SNNLayer, *, hw: SpiNNaker2Config = DEFAULT_S2
) -> int:
    """PE count measured from the drawn weight matrix (per-cell synapse counts)."""
    src_parts = equal_parts(layer.n_source, hw.max_neurons_per_pe)
    tgt_parts = equal_parts(layer.n_target, hw.max_neurons_per_pe)
    n_src_vertex = len(src_parts)
    src_edges = np.cumsum([0] + src_parts)
    tgt_edges = np.cumsum([0] + tgt_parts)
    if is_sparse(layer):
        si, ti, _, _ = layer.coo()     # synapse coordinates, no dense array
    else:
        si, ti = np.nonzero(layer.connectivity())
    # synapse count per (src_part, tgt_part) cell via 2-D histogram
    cell_counts, _, _ = np.histogram2d(si, ti, bins=[src_edges, tgt_edges])
    pes = 0
    for a, sp in enumerate(src_parts):
        for b, tp in enumerate(tgt_parts):
            overhead = serial_pe_overhead(tp, sp, layer.delay_range, n_src_vertex, hw=hw)
            matrix = 4.0 * cell_counts[a, b]
            pes += min(hw.max_matrix_split, _matrix_split_factor(matrix, overhead, hw))
    return int(pes)


def _part_of(idx: np.ndarray, parts: list) -> np.ndarray:
    """The part each index lies in, for :func:`equal_parts` sizes (the
    larger parts first): integer arithmetic, no search."""
    big = parts[0]
    n_big = sum(1 for p in parts if p == big)
    cut = n_big * big
    small = parts[-1] if len(parts) > n_big else big
    return np.where(idx < cut, idx // big, n_big + (idx - cut) // small)


def compile_serial(
    layer: SNNLayer, *, hw: SpiNNaker2Config = DEFAULT_S2
) -> SerialProgram:
    """Emit the full event-driven machine graph for one projection.

    Accepts dense :class:`SNNLayer` and CSR
    :class:`~repro.core.layer.SparseProjection` storage alike; the sparse
    path assigns synapses to cells straight from the COO coordinates and
    never materializes an ``(S, T)`` array.
    """
    src_parts = equal_parts(layer.n_source, hw.max_neurons_per_pe)
    tgt_parts = equal_parts(layer.n_target, hw.max_neurons_per_pe)
    n_src_vertex = len(src_parts)
    src_edges = np.cumsum([0] + src_parts)
    tgt_edges = np.cumsum([0] + tgt_parts)

    sparse = is_sparse(layer)
    if sparse:
        # one stable sort by cell keeps each cell's synapses in the CSR's
        # row-major order and never builds a per-cell mask over all
        # synapses; the rows are packed once and each cell holds a view
        # (per-row and per-target lookups, in the CSR's own dtypes: no
        # widened copy of the synapses)
        counts = np.diff(layer.indptr)
        n_cells = len(src_parts) * len(tgt_parts)
        kt = (np.int64 if n_cells > 1 << 16 else
              np.uint16 if n_cells > 256 else np.uint8)
        tgt_cell = _part_of(np.arange(layer.n_target), tgt_parts).astype(kt)
        row_key = _part_of(np.arange(layer.n_source), src_parts) * len(tgt_parts)
        key = np.repeat(row_key.astype(kt), counts)
        key += tgt_cell[layer.indices]
        order = np.argsort(key, kind="stable")
        cell_bounds = np.zeros(n_cells + 1, np.int64)
        np.cumsum(np.bincount(key, minlength=n_cells), out=cell_bounds[1:])
        del key
        all_src = np.repeat(
            np.arange(layer.n_source, dtype=np.int32), counts)[order]
        tgt = layer.indices[order]
        packed_all = pack_rows(
            layer.values[order], layer.delay_values[order],
            tgt - tgt_edges[tgt_cell[tgt]].astype(tgt.dtype),
        )
        del order, tgt

    cells: List[SerialCell] = []
    for a, sp in enumerate(src_parts):
        s0 = int(src_edges[a])
        # single projection => one master-population-table entry per
        # source vertex; entry = (routing key, address-list offset, len).
        # Other source vertices route to sibling cells; their entries
        # exist in every PE's table (Table I counts n_source_vertex).
        others = [x for x in range(n_src_vertex) if x != a]
        mpt = np.zeros((n_src_vertex, 3), dtype=np.int64)
        mpt[0] = (a, 0, sp)
        mpt[1:, 0] = others
        for b, tp in enumerate(tgt_parts):
            t0 = int(tgt_edges[b])
            if sparse:
                k = a * len(tgt_parts) + b
                lo, hi = int(cell_bounds[k]), int(cell_bounds[k + 1])
                rows_per_src = np.bincount(all_src[lo:hi] - s0, minlength=sp)
                packed = packed_all[lo:hi]
                cell_elems = sp * tp
            else:
                w = layer.weights[s0 : s0 + sp, t0 : t0 + tp]
                d = layer.delays[s0 : s0 + sp, t0 : t0 + tp]
                conn = w != 0.0
                rows_per_src = conn.sum(axis=1)
                si, ti = np.nonzero(conn)
                packed = pack_rows(w[si, ti], d[si, ti], ti)
                cell_elems = w.size

            # one block per source neuron, rows sorted by source
            row_start = np.concatenate([[0], np.cumsum(rows_per_src)[:-1]])
            address_list = np.stack(
                [row_start, rows_per_src], axis=1
            ).astype(np.int64)

            overhead = serial_pe_overhead(tp, sp, layer.delay_range, n_src_vertex, hw=hw)
            matrix_bytes = 4.0 * packed.size
            k = min(
                hw.max_matrix_split,
                _matrix_split_factor(matrix_bytes, overhead, hw),
            )
            cost = serial_pe_cost(
                tp, sp, (packed.size / max(1, cell_elems)), layer.delay_range,
                n_src_vertex, hw=hw, matrix_split=k,
            )
            cells.append(
                SerialCell(
                    src_start=s0, src_size=sp, tgt_start=t0, tgt_size=tp,
                    master_population_table=mpt,
                    address_list=address_list,
                    synaptic_rows=packed,
                    matrix_split=k,
                    cost=cost,
                )
            )
    return SerialProgram(
        layer_name=layer.name,
        n_source=layer.n_source,
        n_target=layer.n_target,
        delay_range=layer.delay_range,
        cells=cells,
    )
