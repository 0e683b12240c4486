"""Pallas TPU kernel: int8 WDM x int8 stacked-spike matmul -> int32.

Hardware adaptation (DESIGN.md §2): SpiNNaker2's MAC array consumes 4x16
tiles of 8-bit operands with 32-bit accumulation.  The TPU analogue is the
MXU: we tile (targets x columns x batch) as (bm x bk x bn) VMEM blocks and
accumulate int8 x int8 -> int32 partial products over the K grid axis,
revisiting the output block (the canonical Pallas reduction layout).
int8 matmuls run at 2x bf16 throughput on v5e.

The weight-delay map is read in place, never padded: the grid is
``(cdiv(M, bm), N / bn, cdiv(K, bk))``, so the last row and column blocks
may reach past the map.  Output rows past M are not written back.  In
the last column block the kernel zeroes the spike rows past K, so the
map's columns there add nothing, whatever the out-of-bounds read held.
The small spike operand is the one padded: to ``cdiv(K, bk) * bk`` rows
and a multiple of ``bn`` lanes.

:func:`wdm_tiles` chooses the blocks from the shapes.  At batch 1 the
product is bound by reading the map from HBM, so the weight blocks are
large (a few MiB: a grid step costs about 0.35 us, so each streams for
several us) and tall (the spike block is re-read once per row block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: int8 sublane tile rows and the lane width (a (32, 128) int8 tile).
SUBLANES, LANES = 32, 128
#: Most rows in one block.  The spike block is read once per row block,
#: so at batch <= 128 a block of >= 1024 rows keeps those reads <= 1/8 of
#: the map's bytes.
BM_MAX = 2048
#: Most lanes in one block: a batch up to this is one lane block, so the
#: map is read once per call.
BN_MAX = 512
#: Target bytes of one int8 weight block.
BLOCK_BYTES = 4 << 20
#: VMEM the pipeline's blocks may take (:func:`vmem_bytes`; v5e has
#: 128 MiB per core).  It bounds the columns of a map with few rows.
VMEM_BUDGET = 32 << 20
#: Room above the blocks' bytes for the compiler's own scratch.
VMEM_HEADROOM = 4 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _balanced(size: int, most: int, align: int) -> int:
    """The smallest ``align``-multiple block that covers ``size`` in as few
    blocks of at most ``most`` as possible (the last block is not mostly
    empty)."""
    return _round_up(pl.cdiv(size, pl.cdiv(size, most)), align)


def vmem_bytes(bm: int, bk: int, bn: int) -> int:
    """VMEM the kernel's blocks take: two buffers each of the weight,
    spike and int32 output blocks, the int32 product, and the tail's int32
    row index and masked spike block (rows padded to the int8 tile)."""
    rows = _round_up(bm, SUBLANES)
    return 2 * rows * bk + 7 * bk * bn + 3 * 4 * rows * bn


def wdm_tiles(m: int, k: int, n: int) -> tuple[int, int, int]:
    """``(bm, bk, bn)`` for an (m, k) map times a (k, n) spike block.

    Rows: the whole map when ``m <= BM_MAX`` (a full-extent block needs no
    alignment), else balanced 32-row multiples up to ``BM_MAX``.  Lanes:
    the batch rounded up to 128, at most ``BN_MAX``.  Columns: 128-multiples
    filling ``BLOCK_BYTES`` of weights, or fewer where the pipeline would
    pass ``VMEM_BUDGET``, balanced over ``k``.
    """
    bn = min(_round_up(max(n, 1), LANES), BN_MAX)
    bm = m if m <= BM_MAX else _balanced(m, BM_MAX, SUBLANES)
    rows = _round_up(bm, SUBLANES)
    fit = (VMEM_BUDGET - vmem_bytes(bm, 0, bn)) // (2 * rows + 7 * bn)
    cols = max(LANES, min(BLOCK_BYTES // rows, fit) // LANES * LANES)
    return bm, _balanced(max(k, 1), cols, LANES), bn


def _matmul_kernel(a_ref, x_ref, o_ref, *, k_tail: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def accumulate(x):
        o_ref[...] += jax.lax.dot_general(
            a_ref[...], x,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

    if not k_tail:
        accumulate(x_ref[...])
        return
    last = pl.num_programs(2) - 1

    @pl.when(k < last)
    def _whole():
        accumulate(x_ref[...])

    @pl.when(k == last)
    def _tail():
        # the map's columns past K hold whatever the edge read left: zero
        # their spikes, and an int8 weight of any value adds 0.  (Masking
        # the weight block instead needs a sublane broadcast that Mosaic
        # lacks for a one-row map.)
        x = x_ref[...]
        row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        accumulate(jnp.where(row < k_tail, x, jnp.zeros_like(x)))


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def spike_wdm_matmul_pallas(
    wdm: jnp.ndarray,       # (M, K) int8, any M and K
    stacked: jnp.ndarray,   # (cdiv(K, bk) * bk, N) int8, N % bn == 0
    *,
    bm: int,
    bn: int,
    bk: int,
    interpret: bool = False,
) -> jnp.ndarray:
    m, k = wdm.shape
    kp, n = stacked.shape
    nk = pl.cdiv(k, bk)
    assert kp == nk * bk and n % bn == 0, (
        f"spikes must cover the column blocks: {(m, k, kp, n)} vs "
        f"{(bm, bk, bn)}"
    )
    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_tail=k % bk),
        grid=(pl.cdiv(m, bm), n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(bm, bk, bn) + VMEM_HEADROOM
        ),
        interpret=interpret,
    )(wdm, stacked)
